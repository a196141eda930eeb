"""Family-dispatching facade: one API for all ten architectures.

The launchers, trainer and server talk to this module only.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.shard.spec import NO_SHARD, ShardCtx

from . import encdec, lm


def init_params(key, cfg):
    if cfg.is_encdec:
        return encdec.init_params(key, cfg)
    return lm.init_params(key, cfg)


def abstract_params(cfg, seed: int = 0):
    """Parameter pytree of ShapeDtypeStructs -- no allocation (abstract shapes)."""
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(seed))


def forward(params, cfg, batch, *, ctx: ShardCtx = NO_SHARD, backend="xla",
            remat="none"):
    """Teacher-forced logits for a training batch dict."""
    if cfg.is_encdec:
        return encdec.forward(params, cfg, batch["src_embeds"], batch["tokens"],
                              ctx=ctx, backend=backend, remat=remat)
    return lm.forward(params, cfg, batch["tokens"], ctx=ctx,
                      prefix_embeds=batch.get("prefix_embeds"),
                      backend=backend, remat=remat)


def init_cache(cfg, batch_size, max_len, src_len: Optional[int] = None, dtype=None):
    if cfg.is_encdec:
        return encdec.init_cache(cfg, batch_size, max_len, src_len or max_len, dtype)
    return lm.init_cache(cfg, batch_size, max_len, dtype)


def prefill(params, cfg, batch, cache, *, ctx: ShardCtx = NO_SHARD, backend="xla"):
    if cfg.is_encdec:
        return encdec.prefill(params, cfg, batch["src_embeds"], batch["tokens"],
                              cache, ctx=ctx, backend=backend)
    return lm.prefill(params, cfg, batch["tokens"], cache,
                      prefix_embeds=batch.get("prefix_embeds"),
                      ctx=ctx, backend=backend)


def decode_step(params, cfg, token, cache, *, ctx: ShardCtx = NO_SHARD, backend="xla"):
    if cfg.is_encdec:
        return encdec.decode_step(params, cfg, token, cache, ctx=ctx, backend=backend)
    return lm.decode_step(params, cfg, token, cache, ctx=ctx, backend=backend)


# ---------------------------------------------------------------------------
# Modality frontend stubs (per the brief: precomputed frame/patch embeddings)
# ---------------------------------------------------------------------------


def frontend_stub_embeds(cfg, batch, seq, key=None):
    """Synthetic frontend output: (batch, seq, d_model) unit-scale embeds."""
    key = jax.random.key(0) if key is None else key
    from .layers import dtype_of

    return jax.random.normal(key, (batch, seq, cfg.d_model), dtype_of(cfg.dtype))
