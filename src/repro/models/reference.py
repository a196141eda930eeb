"""Plain float32 references of the layers the system runs through kernels.

DeepSeek-V3's MoE layer, written from its published description (the
model's ``config.json`` and the router of its inference code: sigmoid
scores, ``noaux_tc`` group-limited top-k, normalized weights scaled by
``routed_scaling_factor``) in straightforward ``jax.numpy``: float32 at
``highest`` matmul precision, every held expert applied to every token
and weighted by a dense (T, n_experts) gate that is zero where an expert
was not chosen.  No kernel, no tile space, no capacity, nothing dropped.
Ranks come from sorts, not from ``top_k``, so the program's router is
checked against another formulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rank_desc(a):
    """Rank of each entry along the last axis, 0 for the largest; ties to
    the lower index."""
    return jnp.argsort(jnp.argsort(-a, axis=-1, stable=True), axis=-1)


def moe_gates_reference(x, router, bias, cfg):
    """(T, n_experts) float32 routing weights, zero off the chosen experts."""
    E, G = cfg.n_experts, cfg.n_group
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x.astype(jnp.float32)
                           @ router.astype(jnp.float32))
    choice = s + bias
    grouped = choice.reshape(-1, G, E // G)
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    in_group = jnp.repeat(_rank_desc(group_score) < cfg.topk_group,
                          E // G, axis=-1)
    chosen = _rank_desc(jnp.where(in_group, choice, -jnp.inf)) < cfg.top_k
    w = jnp.where(chosen, s, 0.0)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor


def experts_reference(x, w_gate, w_up, w_down, gates, held):
    """The held experts' part: sum over ``held`` of ``gates[:, e]`` times
    ``down(silu(x @ Wg) * (x @ Wu))``, float32.  x (T, d)."""
    x = x.astype(jnp.float32)
    y = jnp.zeros(x.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i, e in enumerate(held):
            h = jax.nn.silu(x @ w_gate[i].astype(jnp.float32)) \
                * (x @ w_up[i].astype(jnp.float32))
            y = y + gates[:, e:e + 1] * (h @ w_down[i].astype(jnp.float32))
    return y


def moe_layer_reference(params, x, cfg, *, held):
    """The layer on a chip that holds ``held`` (``layers.moe_held_block``'s
    parameters): its routed experts' part plus the shared expert.
    x (B, T, d); float32 out."""
    B, T, d = x.shape
    flat = x.reshape(B * T, d)
    gates = moe_gates_reference(flat, params["router"], params["bias"], cfg)
    y = experts_reference(flat, params["wg"], params["wu"], params["wd"],
                          gates, held)
    sh = params["shared"]
    x32 = flat.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = jax.nn.silu(x32 @ sh["wg"].astype(jnp.float32)) \
            * (x32 @ sh["wu"].astype(jnp.float32))
        y = y + h @ sh["wd"].astype(jnp.float32)
    return y.reshape(B, T, d)
