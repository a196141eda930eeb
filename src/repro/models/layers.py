"""Transformer building blocks: RMSNorm, RoPE, GQA attention, MLP, MoE.

Pure-functional (params are plain pytrees of jnp arrays); every block takes
an explicit ``ShardCtx`` so the same code runs unsharded on CPU and
TP/FSDP-sharded on the production mesh.

Attention has two execution backends:
  * "xla"    -- einsum attention (default; what the dry-run compiles)
  * "pallas" -- the fused flash-attention kernel (TPU production path;
                validated in interpret mode by tests)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.shard.spec import NO_SHARD, ShardCtx, cs

NEG_INF = -1e30


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = (fan_in ** -0.5) if scale is None else scale
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm(x, w, eps=1e-5):
    # variance/rsqrt in f32 (precision); the (T, d)-sized multiply applies in
    # x.dtype.  (Computing the square in bf16 was tried and REFUTED: it
    # shifted XLA fusion boundaries and increased measured traffic -- see
    # EXPERIMENTS.md §Perf P6.)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return (x * scale) * w


def rmsnorm_init(d, dtype=jnp.float32):
    return jnp.ones((d,), dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_cos_sin(positions, head_dim, theta=10_000.0):
    """positions (...,) int -> cos/sin (..., head_dim//2) f32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (B, T, H, D); cos/sin (B?, T, D//2) or (T, D//2).

    Angles are generated in f32 (rope_cos_sin); the (T, H, D)-sized rotation
    itself runs in x.dtype so the q/k streams (and their cotangents) stay
    bf16 at fusion boundaries -- f32 rope quadrupled the residual-sized HBM
    traffic of every attention layer (EXPERIMENTS.md §Perf P6).
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # insert the head axis; leading (batch) axes broadcast from the left
    cos = cos[..., None, :].astype(x.dtype)
    sin = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA, causal / SWA / cross, optional KV cache)
# ---------------------------------------------------------------------------


#: self-attention switches to the chunked online-softmax path (flash-style,
#: pure XLA: double scan over q/kv blocks, O(T*blk) memory) above this
#: length.  Tuned in EXPERIMENTS.md §Perf: at 4k the dense scores fit and
#: cost *less* HBM traffic than the scan-block boundaries, so the chunked
#: path only pays off from 32k (where dense cannot fit at all); on real TPU
#: the Pallas kernel replaces both.
CHUNKED_ATTN_THRESHOLD = 8192
CHUNK_BLK_Q = 1024
CHUNK_BLK_K = 1024


def _blk_mask(rows, cols, Tq, Tk, causal, window):
    mask = (cols < Tk) & (rows < Tq)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    return mask


def _flash_fwd_core(q, k, v, causal, window, row0, blk_q, blk_k):
    """Returns (o (B,Tq,H,D), lse (B,Hkv,g,Tq_pad)) -- online softmax over
    kv blocks, scanned over q blocks; scores never reach HBM whole."""
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    scale = D ** -0.5
    nq, nk = -(-Tq // blk_q), -(-Tk // blk_k)
    qp = jnp.pad(q, ((0, 0), (0, nq * blk_q - Tq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * blk_k - Tk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * blk_k - Tk), (0, 0), (0, 0)))
    qs = jnp.moveaxis(qp.reshape(B, nq, blk_q, H, D), 1, 0)
    ks = jnp.moveaxis(kp.reshape(B, nk, blk_k, Hkv, D), 1, 0)
    vs = jnp.moveaxis(vp.reshape(B, nk, blk_k, Hkv, D), 1, 0)

    def q_block(_, qi_qb):
        qi, qb = qi_qb  # qb (B, blk_q, H, D)
        qf = (qb * jnp.asarray(scale, qb.dtype)).reshape(B, blk_q, Hkv, group, D)
        rows = row0 + qi * blk_q + jnp.arange(blk_q)[:, None]

        def kv_block(carry, ki_kv):
            m_p, l_p, acc = carry
            ki, kb, vb = ki_kv
            s = jnp.einsum("btkgd,bskd->bkgts", qf, kb,
                           preferred_element_type=jnp.float32)
            cols = ki * blk_k + jnp.arange(blk_k)[None, :]
            mask = _blk_mask(rows, cols, row0 + Tq, Tk, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_c = jnp.max(s, axis=-1)
            m_n = jnp.maximum(m_p, m_c)
            p = jnp.exp(s - m_n[..., None]) * mask[None, None, None]
            alpha = jnp.exp(m_p - m_n)
            l_n = alpha * l_p + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgts,bskd->bkgtd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_n, l_n, acc), None

        m0 = jnp.full((B, Hkv, group, blk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, group, blk_q), jnp.float32)
        a0 = jnp.zeros((B, Hkv, group, blk_q, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (jnp.arange(nk), ks, vs))
        o = acc / jnp.where(l > 0, l, 1.0)[..., None]
        o = jnp.moveaxis(o, 3, 1).reshape(B, blk_q, H, D)
        # +inf for fully-masked rows => bwd p = exp(s - inf) = 0 (no NaNs)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-38)), jnp.inf)
        return None, (o.astype(q.dtype), lse)

    _, (ob, lse_b) = jax.lax.scan(q_block, None, (jnp.arange(nq), qs))
    out = jnp.moveaxis(ob, 0, 1).reshape(B, nq * blk_q, H, D)[:, :Tq]
    # lse blocks (nq, B, Hkv, g, blk_q) -> (B, Hkv, g, Tq_pad)
    lse = jnp.moveaxis(lse_b, 0, 3).reshape(B, Hkv, group, nq * blk_q)
    return out, lse


def _flash_bwd_core(q, k, v, o, lse, do, causal, window, row0, blk_q, blk_k):
    """FlashAttention backward: recompute p per block; O(T*d) residuals."""
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    scale = D ** -0.5
    nq, nk = -(-Tq // blk_q), -(-Tk // blk_k)
    qp = jnp.pad(q, ((0, 0), (0, nq * blk_q - Tq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * blk_k - Tk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * blk_k - Tk), (0, 0), (0, 0)))
    dop = jnp.pad(do, ((0, 0), (0, nq * blk_q - Tq), (0, 0), (0, 0)))
    op = jnp.pad(o, ((0, 0), (0, nq * blk_q - Tq), (0, 0), (0, 0)))
    # Di = rowsum(do * o): (B, Hkv, g, Tq_pad)
    Df = jnp.einsum("btkgd,btkgd->bkgt",
                    dop.reshape(B, nq * blk_q, Hkv, group, D),
                    op.reshape(B, nq * blk_q, Hkv, group, D),
                    preferred_element_type=jnp.float32)

    qs = jnp.moveaxis(qp.reshape(B, nq, blk_q, H, D), 1, 0)
    dos = jnp.moveaxis(dop.reshape(B, nq, blk_q, H, D), 1, 0)
    ks = jnp.moveaxis(kp.reshape(B, nk, blk_k, Hkv, D), 1, 0)
    vs = jnp.moveaxis(vp.reshape(B, nk, blk_k, Hkv, D), 1, 0)
    lse_s = jnp.moveaxis(lse.reshape(B, Hkv, group, nq, blk_q), 3, 0)
    D_s = jnp.moveaxis(Df.reshape(B, Hkv, group, nq, blk_q), 3, 0)

    def kv_step(dq_acc, ki_kv):
        ki, kb, vb = ki_kv
        cols = ki * blk_k + jnp.arange(blk_k)[None, :]

        def q_step(carry, xs):
            dk_b, dv_b = carry
            qi, qb, dob, lseb, Db = xs
            qf = qb.reshape(B, blk_q, Hkv, group, D)
            dof = dob.reshape(B, blk_q, Hkv, group, D)
            rows = row0 + qi * blk_q + jnp.arange(blk_q)[:, None]
            mask = _blk_mask(rows, cols, row0 + Tq, Tk, causal, window)
            s = jnp.einsum("btkgd,bskd->bkgts", qf, kb,
                           preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s - lseb[..., None]) * mask[None, None, None]
            pb = p.astype(qb.dtype)
            dv_b = dv_b + jnp.einsum("bkgts,btkgd->bskd", pb, dof,
                                     preferred_element_type=jnp.float32)
            dp = jnp.einsum("btkgd,bskd->bkgts", dof, vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - Db[..., None]) * scale
            dsb = ds.astype(qb.dtype)
            dk_b = dk_b + jnp.einsum("bkgts,btkgd->bskd", dsb, qf,
                                     preferred_element_type=jnp.float32)
            dq_b = jnp.einsum("bkgts,bskd->btkgd", dsb, kb,
                              preferred_element_type=jnp.float32).reshape(
                B, blk_q, H, D)
            return (dk_b, dv_b), dq_b

        zk = jnp.zeros((B, blk_k, Hkv, D), jnp.float32)
        (dk_b, dv_b), dq_blocks = jax.lax.scan(
            q_step, (zk, zk), (jnp.arange(nq), qs, dos, lse_s, D_s))
        return dq_acc + dq_blocks, (dk_b, dv_b)

    dq0 = jnp.zeros((nq, B, blk_q, H, D), jnp.float32)
    dq_acc, (dk_blocks, dv_blocks) = jax.lax.scan(
        kv_step, dq0, (jnp.arange(nk), ks, vs))
    dq = jnp.moveaxis(dq_acc, 0, 1).reshape(B, nq * blk_q, H, D)[:, :Tq]
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(B, nk * blk_k, Hkv, D)[:, :Tk]
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(B, nk * blk_k, Hkv, D)[:, :Tk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


import functools as _ft


@_ft.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_xla(q, k, v, causal, window, row0, blk_q, blk_k):
    return _flash_fwd_core(q, k, v, causal, window, row0, blk_q, blk_k)[0]


def _flash_xla_fwd(q, k, v, causal, window, row0, blk_q, blk_k):
    o, lse = _flash_fwd_core(q, k, v, causal, window, row0, blk_q, blk_k)
    return o, (q, k, v, o, lse)


def _flash_xla_bwd(causal, window, row0, blk_q, blk_k, res, do):
    q, k, v, o, lse = res
    return _flash_bwd_core(q, k, v, o, lse, do, causal, window, row0,
                           blk_q, blk_k)


_flash_xla.defvjp(_flash_xla_fwd, _flash_xla_bwd)


def _sdpa_chunked(q, k, v, *, causal, window, row0=0,
                  blk_q=CHUNK_BLK_Q, blk_k=CHUNK_BLK_K):
    """Flash-style attention in pure XLA with a flash *backward* too.

    Forward: double scan (q blocks x kv blocks) with online softmax -- the
    (Tq, Tk) score matrix never reaches HBM.  Backward: custom VJP that
    recomputes p per block (residuals are O(T*d): q, k, v, o, lse), the
    standard FlashAttention dq/dk/dv two-scan.  When ``row0`` is traced
    (prefill against a cache at a dynamic position -- an inference path, no
    grads), the plain forward core is used directly.
    """
    if isinstance(row0, int):
        return _flash_xla(q, k, v, causal, window, row0, blk_q, blk_k)
    return _flash_fwd_core(q, k, v, causal, window, row0, blk_q, blk_k)[0]


def attention_init(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, cfg.n_heads * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, d), dtype=dtype),
    }


def _sdpa_xla(q, k, v, *, causal, window, row_pos=None, col_pos=None):
    """q (B,Tq,H,D), k/v (B,Tk,Hkv,D).  Dense masked attention, f32 accum.

    ``row_pos``/``col_pos`` are the *absolute* token positions of queries and
    keys (defaults: 0..Tq-1 / 0..Tk-1).  Ring-buffer caches pass permuted /
    partially-negative ``col_pos`` (negative = slot never written).
    """
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    qf = q * jnp.asarray(D ** -0.5, q.dtype)
    # (B, Hkv, group, Tq, Tk): bf16 operands, f32 MXU accumulation
    s = jnp.einsum(
        "btkgd,bskd->bkgts",
        qf.reshape(B, Tq, Hkv, group, D), k,
        preferred_element_type=jnp.float32,
    )
    rows = (jnp.arange(Tq) if row_pos is None else row_pos)[:, None]
    cols = (jnp.arange(Tk) if col_pos is None else col_pos)[None, :]
    mask = cols >= 0
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Tq, H, D).astype(q.dtype)


def attention_block(
    params,
    x,  # (B, T, d)
    cfg,
    *,
    ctx: ShardCtx = NO_SHARD,
    positions=None,  # (T,) or (B, T) absolute positions for RoPE
    causal: bool = True,
    kv_cache: Optional[dict] = None,  # {"k","v": (B,S,Hkv,hd)}
    cache_pos=None,  # scalar: current length of the cache
    xattn_kv=None,  # (B, S_src, d) encoder output for cross-attention
    backend: str = "xla",
):
    """Returns (out (B,T,d), updated_cache | None)."""
    B, T, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    q = (x @ params["wq"]).reshape(B, T, H, hd)
    kv_src = xattn_kv if xattn_kv is not None else x
    k = (kv_src @ params["wk"]).reshape(B, kv_src.shape[1], Hkv, hd)
    v = (kv_src @ params["wv"]).reshape(B, kv_src.shape[1], Hkv, hd)
    q = cs(q, "batch", None, "model", None, ctx=ctx)
    k = cs(k, "batch", None, "model", None, ctx=ctx)
    v = cs(v, "batch", None, "model", None, ctx=ctx)

    if xattn_kv is None:  # RoPE only for self-attention
        if positions is None:
            positions = jnp.arange(T)
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    new_cache = None
    row_pos = col_pos = None
    row0 = 0
    if kv_cache is not None:
        pos = cache_pos
        S_c = kv_cache["k"].shape[1]
        tail = min(T, S_c)  # only the last S_c tokens can survive in a ring
        if tail == T and cfg.window is None:
            # plain append cache (no SWA): positions == slots
            ck = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["k"], k.astype(kv_cache["k"].dtype), pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["v"], v.astype(kv_cache["v"].dtype), pos, axis=1)
            slots = jnp.arange(S_c)
            col_pos = jnp.where(slots < pos + T, slots, -1)
        else:
            # ring buffer (SWA): slot of absolute position a is a % S_c
            idx = (pos + T - tail + jnp.arange(tail)) % S_c
            ck = kv_cache["k"].at[:, idx].set(k[:, T - tail :].astype(kv_cache["k"].dtype))
            cv = kv_cache["v"].at[:, idx].set(v[:, T - tail :].astype(kv_cache["v"].dtype))
            slots = jnp.arange(S_c)
            # absolute position held by each slot (negative = never written)
            col_pos = (pos + T - 1) - ((pos + T - 1 - slots) % S_c)
        new_cache = {"k": ck, "v": cv}
        if T > 1:
            # prefill: attend over this call's own keys (banded/causal).
            # The cache cannot serve early queries in the ring case (later
            # keys overwrite theirs), and in the append case the live k/v
            # are identical to the cache content anyway.  Assumes prefill
            # starts at pos=0 (chunked prefill would concat ring+current).
            col_pos = None  # cols are this call's 0..T-1 (+row0 below)
            row0 = pos
        else:
            k, v = ck, cv
        row_pos = pos + jnp.arange(T)

    if backend == "pallas" and kv_cache is None and xattn_kv is None:
        from repro.kernels import flash_attention

        o = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=causal, window=cfg.window,
        ).transpose(0, 2, 1, 3)
    elif (T > 1 and col_pos is None
          and k.shape[1] >= (4096 if cfg.d_model >= 8192
                             else CHUNKED_ATTN_THRESHOLD)):
        # very wide models (deepseek-67b) take the flash path already at 4k:
        # their dense-attention residuals alone overflow HBM (§Perf)
        # long attention (32k+ prefill/train, self or cross): flash-style
        # chunked path -- never materializes (Tq, Tk) scores
        o = _sdpa_chunked(
            q, k, v,
            causal=causal and xattn_kv is None,
            window=cfg.window if xattn_kv is None else None,
            row0=row0)
    else:
        o = _sdpa_xla(
            q, k, v,
            causal=causal and xattn_kv is None,
            window=cfg.window if xattn_kv is None else None,
            row_pos=row_pos, col_pos=col_pos,
        )
    o = cs(o, "batch", None, "model", None, ctx=ctx)
    out = o.reshape(B, T, H * hd) @ params["wo"]
    return cs(out, "batch", None, None, ctx=ctx), new_cache


def project_kv(params, src, cfg):
    """Precompute cross-attention K/V from encoder output (no RoPE)."""
    B, S, _ = src.shape
    k = (src @ params["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    v = (src @ params["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    return k, v


def attention_with_kv(params, x, k, v, cfg, *, ctx: ShardCtx = NO_SHARD):
    """Cross-attention against precomputed K/V (decode-time path)."""
    B, T, _ = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q = (x @ params["wq"]).reshape(B, T, H, hd)
    q = cs(q, "batch", None, "model", None, ctx=ctx)
    if T > 1 and k.shape[1] >= CHUNKED_ATTN_THRESHOLD:
        o = _sdpa_chunked(q, k, v, causal=False, window=None)
    else:
        o = _sdpa_xla(q, k, v, causal=False, window=None)
    out = o.reshape(B, T, H * hd) @ params["wo"]
    return cs(out, "batch", None, None, ctx=ctx)


# ---------------------------------------------------------------------------
# Gated MLP (llama-style SwiGLU)
# ---------------------------------------------------------------------------


def mlp_init(key, d, ff, dtype):
    ks = jax.random.split(key, 3)
    return {
        "wg": dense_init(ks[0], (d, ff), dtype=dtype),
        "wu": dense_init(ks[1], (d, ff), dtype=dtype),
        "wd": dense_init(ks[2], (ff, d), dtype=dtype),
    }


def mlp_block(params, x, *, ctx: ShardCtx = NO_SHARD):
    h = jax.nn.silu(x @ params["wg"]) * (x @ params["wu"])
    h = cs(h, "batch", None, "model", ctx=ctx)
    out = h @ params["wd"]
    return cs(out, "batch", None, None, ctx=ctx)


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k routing, capacity-based, expert-parallel layout)
# ---------------------------------------------------------------------------


def moe_init(key, cfg, dtype):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), dtype=jnp.float32),
        "wg": dense_init(ks[1], (E, d, ff), dtype=dtype),
        "wu": dense_init(ks[2], (E, d, ff), dtype=dtype),
        "wd": dense_init(ks[3], (E, ff, d), dtype=dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.d_ff * cfg.n_shared_experts, dtype)
    return p


def moe_block(params, x, cfg, *, ctx: ShardCtx = NO_SHARD):
    """Top-k capacity MoE with **group-local dispatch** (standard EP layout).

    Tokens are processed in G groups (G = the data-parallel degree): each
    group routes its own tokens, computes position-in-expert with a
    group-local cumsum, and gathers/scatters only within the group -- so
    under GSPMD nothing token-sized ever crosses the data axis.  The only
    cross-device movement is the (group -> expert) transpose of the slot
    tensor: the EP all-to-all.  Per-group capacity C_g = cf*K*N_g/E
    (overflow dropped -- training-time approximation; small-N calls are
    floored dropless for decode).

    A naive *global* dispatch (one cumsum over all N tokens) forces every
    shard to materialize the full token table per layer per microbatch --
    measured at 4+ TiB/device/step of all-reduce on qwen3 (§Perf P5).
    """
    B, T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * T
    G = ctx.batch_size_product if (ctx.enabled and N >= 4096) else 1
    while N % G:  # awkward batch extents: fall back to fewer groups
        G //= 2
    n = N // G  # tokens per group
    xg = x.reshape(G, n, d)
    xg = cs(xg, "batch", None, None, ctx=ctx)

    gates = jax.nn.softmax(
        (xg.astype(jnp.float32) @ params["router"]), axis=-1)  # (G, n, E)
    top_w, top_e = jax.lax.top_k(gates, K)  # (G, n, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    C = int(max(1, round(cfg.capacity_factor * K * n / E), min(n, 256)))
    flat_e = top_e.reshape(G, n * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (G, n*K, E)
    pos = jnp.cumsum(onehot, axis=1) * onehot - 1  # group-local positions
    pos_in_e = pos.max(axis=-1)  # (G, n*K)
    keep = pos_in_e < C

    # group-local slot table: token row n = empty (points at the pad row)
    tok_ids = jnp.broadcast_to(
        jnp.repeat(jnp.arange(n, dtype=jnp.int32), K)[None], (G, n * K))
    slot_tok = jnp.full((G, E, C), n, jnp.int32)
    gidx = jnp.broadcast_to(jnp.arange(G, dtype=jnp.int32)[:, None], (G, n * K))
    slot_tok = slot_tok.at[
        gidx,
        jnp.where(keep, flat_e, E),  # dropped -> out of bounds, mode="drop"
        jnp.where(keep, pos_in_e, C),
    ].set(tok_ids, mode="drop")

    xpad = jnp.concatenate([xg, jnp.zeros((G, 1, d), xg.dtype)], axis=1)
    xe = jnp.take_along_axis(
        xpad, slot_tok.reshape(G, E * C, 1), axis=1)  # group-local gather
    xe = xe.reshape(G, E, C, d).transpose(1, 0, 2, 3)  # (E, G, C, d): EP a2a
    xe = cs(xe, "model", "batch", None, None, ctx=ctx)

    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe, params["wg"]))
    h = h * jnp.einsum("egcd,edf->egcf", xe, params["wu"])
    h = cs(h, "model", "batch", None, None, ctx=ctx)
    ye = jnp.einsum("egcf,efd->egcd", h, params["wd"])  # (E, G, C, d)
    ye = ye.transpose(1, 0, 2, 3).reshape(G, E * C, d)  # back: second a2a
    ye = cs(ye, "batch", None, None, ctx=ctx)

    # combine (group-local): gather each pair's slot output, weight, sum K
    w_flat = jnp.where(keep, top_w.reshape(G, n * K), 0.0)  # (G, n*K)
    slot_of_pair = jnp.where(keep, flat_e * C + pos_in_e, E * C)  # (G, n*K)
    ye_pad = jnp.concatenate([ye, jnp.zeros((G, 1, d), ye.dtype)], axis=1)
    y_pairs = jnp.take_along_axis(
        ye_pad, slot_of_pair.reshape(G, n * K, 1), axis=1)
    y_pairs = y_pairs * w_flat[..., None].astype(ye.dtype)
    y = y_pairs.reshape(G, n, K, d).sum(axis=2)

    out = y.reshape(B, T, d).astype(x.dtype)
    if cfg.n_shared_experts:
        # NB: must be called on the (B, T, d) view -- a flat (1, N, d) view
        # would hang the batch sharding on the dummy leading dim and
        # replicate every token's shared-expert compute across the data axis
        # (16x per-device FLOPs; see EXPERIMENTS.md §Perf P5).
        out = out + mlp_block(params["shared"], x, ctx=ctx)
    return cs(out, "batch", None, None, ctx=ctx)


# ---------------------------------------------------------------------------
# DeepSeek-V3 routed experts: one chip's share under expert parallelism
# ---------------------------------------------------------------------------


@_ft.partial(jax.jit, static_argnames=("cfg",))
def moe_route(x, router, bias, cfg):
    """DeepSeek-V3's group-limited router: ``(expert_ids, expert_w)``,
    each (T, top_k), over all ``cfg.n_experts`` experts.

    x (T, d) and router (d, n_experts) in the config's dtype, the matmul
    accumulated in float32; ``bias`` (n_experts,) is the per-expert
    correction bias (``e_score_correction_bias``), which steers the
    choice and not the weights.  Scores are sigmoids; each of the
    ``n_group`` groups is scored by the sum of its top 2 biased scores,
    the top-k experts are chosen among the best ``topk_group`` groups, and
    their unbiased scores are normalized and scaled by
    ``routed_scaling_factor``.
    """
    T = x.shape[0]
    E, G = cfg.n_experts, cfg.n_group
    scores = jax.nn.sigmoid(
        jnp.dot(x, router, preferred_element_type=jnp.float32))
    choice = scores + bias
    # each group's two best scores by two reductions, not a sort
    grouped = choice.reshape(T, G, E // G)
    first = jnp.argmax(grouped, axis=-1)[..., None]
    second = jnp.where(jnp.arange(E // G) == first, -jnp.inf, grouped)
    top2 = grouped.max(-1) + second.max(-1)
    groups = jax.lax.top_k(top2, cfg.topk_group)[1]
    keep = (groups[:, :, None] == jnp.arange(G)).any(1)
    masked = jnp.where(jnp.repeat(keep, E // G, axis=1), choice, -jnp.inf)
    picked, ids = jax.lax.top_k(masked, cfg.top_k)
    # the unbiased scores of the chosen: the bias comes off by a compare
    # with the small bias table, where a gather of T*top_k scores from
    # the (T, n_experts) table costs ms on the chip
    w = picked - jnp.where(ids[..., None] == jnp.arange(E), bias, 0.0).sum(-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    return ids.astype(jnp.int32), w


def moe_held_init(key, cfg, held, dtype):
    """One chip's share of a DeepSeek-V3 MoE layer: the router over all
    experts with its (zero) correction bias, the weights of the ``held``
    experts, and the shared expert."""
    d, ff, E = cfg.d_model, cfg.d_ff, len(held)
    ks = jax.random.split(key, 5)
    return {
        "router": dense_init(ks[0], (d, cfg.n_experts), dtype=dtype),
        "bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        "wg": dense_init(ks[1], (E, d, ff), scale=d ** -0.5, dtype=dtype),
        "wu": dense_init(ks[2], (E, d, ff), scale=d ** -0.5, dtype=dtype),
        "wd": dense_init(ks[3], (E, ff, d), scale=ff ** -0.5, dtype=dtype),
        "shared": mlp_init(ks[4], d, ff * cfg.n_shared_experts, dtype),
    }


def moe_held_block(params, x, cfg, *, held, technique: str = "gss",
                   workers: int = 8, blk: int = 256):
    """A DeepSeek-V3 MoE layer on a chip that holds the experts ``held``.

    Routes every token over all experts (``moe_route``), computes the held
    experts' part dropless through the self-scheduled expert kernel
    (``kernels.moe_experts_persistent``: (expert, row-block) tiles claimed
    through the device protocol), and adds the shared expert.  What the
    experts held elsewhere add is left out: that partial result is what
    this chip gives the layer.  x (B, T, d).
    """
    from repro.kernels import moe_experts_persistent

    B, T, d = x.shape
    flat = x.reshape(B * T, d)
    ids, w = moe_route(flat, params["router"], params["bias"], cfg)
    y, _ = moe_experts_persistent(
        flat, params["wg"], params["wu"], params["wd"], ids, w, held=held,
        technique=technique, workers=workers, blk=blk)
    return (y + mlp_block(params["shared"], flat)).reshape(B, T, d)
