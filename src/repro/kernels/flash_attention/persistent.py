"""Persistent self-scheduled attention over variable-length batches.

The static grid (``ops.flash_attention``) gives every (head, q-block) the
same kv extent, so a varlen batch makes short sequences idle while long
ones grind -- the exact imbalance profile the paper's protocol targets.
Here the loop is the linearized (batch*heads, q-block) tile space and the
per-tile cost is its *actual* kv-block count (``varlen_tile_costs``): the
device claim loop (``repro.device``, DESIGN.md Sec. 14) hands variable
chunks of tiles to a fixed fleet of persistent programs, each of which
runs online-softmax attention with a *traced* kv trip count -- work
proportional to the sequence actually attended, not the padded maximum.

Scope: causal or full attention with GQA and per-batch ``lengths``;
sliding-window masking stays on the static path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing
from repro.device.persistent import DeviceSchedule, claim_schedule

from .kernel import NEG_INF


def _persistent_kernel(
    nclaims_ref,  # (W,)   int32 SMEM
    starts_ref,   # (W*C,) int32 SMEM
    sizes_ref,    # (W*C,) int32 SMEM
    len_ref,      # (B,)   int32 SMEM -- valid kv length per batch row
    q_ref,        # (B*H,   nq*blk_q, D)
    k_ref,        # (B*Hkv, nk*blk_k, D)
    v_ref,        # (B*Hkv, nk*blk_k, D)
    o_ref,        # (B*H, nq*blk_q, D)
    *,
    scale: float,
    causal: bool,
    seq_q: int,
    blk_q: int,
    blk_k: int,
    H: int,
    Hkv: int,
    nq: int,
    D: int,
    C: int,
):
    w = pl.program_id(0)
    group = H // Hkv

    def tile_body(tile):
        bh = tile // nq
        qi = tile - bh * nq
        b = bh // H
        kv = b * Hkv + (bh - b * H) // group
        q_start = pl.multiple_of(qi * blk_q, blk_q)
        len_b = len_ref[b]
        # traced kv trip count: only the blocks this tile actually attends
        limit = jnp.minimum(len_b, q_start + blk_q) if causal else len_b
        jmax = (limit + blk_k - 1) // blk_k

        q = q_ref[bh, pl.ds(q_start, blk_q), :].astype(jnp.float32) * scale
        rows1 = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)

        def kv_body(j, carry):
            m_prev, l_prev, acc = carry
            k_start = pl.multiple_of(j * blk_k, blk_k)
            k = k_ref[kv, pl.ds(k_start, blk_k), :].astype(jnp.float32)
            v = v_ref[kv, pl.ds(k_start, blk_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (blk_q, blk_k)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            mask = (rows1 < seq_q) & (cols < len_b)
            if causal:
                mask &= cols <= rows1
            s = jnp.where(mask, s, NEG_INF)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            # mask multiply: fully-masked rows keep l == 0 (zeros on flush)
            p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        init = (jnp.full((blk_q, 1), NEG_INF, jnp.float32),
                jnp.zeros((blk_q, 1), jnp.float32),
                jnp.zeros((blk_q, D), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, jmax, kv_body, init)
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[bh, pl.ds(q_start, blk_q), :] = (acc / safe).astype(o_ref.dtype)

    def claim_body(c, _):
        st = starts_ref[w * C + c]

        def step(t, __):
            tile_body(st + t)
            return __

        jax.lax.fori_loop(0, sizes_ref[w * C + c], step, 0)
        return _

    jax.lax.fori_loop(0, nclaims_ref[w], claim_body, 0)


def varlen_tile_costs(lengths, H: int, nq: int, blk_q: int, blk_k: int,
                      causal: bool = True):
    """kv blocks actually visited per (batch*head, q-block) tile.

    Row-major over ``B*H*nq`` tiles, matching the persistent kernel's
    linearization -- the cost model the device claim loop balances on.
    """
    lengths = np.asarray(lengths, np.int64)
    B = len(lengths)
    costs = np.zeros(B * H * nq, np.float64)
    for tile in range(B * H * nq):
        b = tile // (H * nq)
        qi = tile % nq
        limit = min(lengths[b], (qi + 1) * blk_q) if causal else lengths[b]
        costs[tile] = max(-(-int(limit) // blk_k), 0)
    return costs


def _vmem_bytes(x) -> int:
    """Bytes of ``x`` as a VMEM block: the minor dim fills 128 lanes."""
    *lead, d = x.shape
    return math.prod(lead) * -(-d // 128) * 128 * x.dtype.itemsize


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "blk_q", "blk_k", "interpret"))
def persistent_call(nclaims, starts, sizes, lengths, q, k, v, *,
                    causal: bool, scale: float, blk_q: int, blk_k: int,
                    interpret: bool):
    """The persistent kernel's ``pallas_call``: jittable, arrays in and out.

    ``nclaims (W,)``, ``starts``/``sizes (W, C)`` are the per-worker claim
    tables and ``lengths (B,)`` the kv extents, all int32; they ride in
    SMEM as scalar-prefetch operands, because each program reads them one
    entry at a time.  q, k and v are whole-array VMEM blocks, so VMEM
    capacity caps the batch; the limit asked of the compiler is what
    those blocks take.  Returns ``(B, H, Tq, D)``.
    """
    B, H, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    assert H % Hkv == 0, "GQA requires H divisible by Hkv"
    workers, C = starts.shape

    nq = -(-Tq // blk_q)
    nk = -(-Tk // blk_k)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nq * blk_q - Tq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, nk * blk_k - Tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, nk * blk_k - Tk), (0, 0)))
    qp = qp.reshape(B * H, nq * blk_q, D)
    kp = kp.reshape(B * Hkv, nk * blk_k, D)
    vp = vp.reshape(B * Hkv, nk * blk_k, D)

    kern = functools.partial(
        _persistent_kernel,
        scale=float(scale), causal=causal, seq_q=Tq,
        blk_q=blk_q, blk_k=blk_k, H=H, Hkv=Hkv, nq=nq, D=D, C=C,
    )

    def whole(x):
        return pl.BlockSpec(x.shape, lambda w, *_: (0,) * x.ndim)

    # a block whose index never moves is held once; the f32 temporaries
    # of one (q-block, kv-block) step need a few MiB on top
    block_bytes = sum(map(_vmem_bytes, (qp, kp, vp, qp)))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(workers,),
            in_specs=[whole(qp), whole(kp), whole(vp)],
            # one shared output block: the claims partition the tile
            # space, so together the workers write every (bh, q-block)
            # slab exactly once
            out_specs=whole(qp),
        ),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=block_bytes + (4 << 20)),
        interpret=interpret,
        name="attention_persistent",
    )(nclaims, starts.reshape(-1), sizes.reshape(-1), lengths, qp, kp, vp)
    return out.reshape(B, H, nq * blk_q, D)[:, :, :Tq, :]


def flash_attention_persistent(
    q,  # (B, H, Tq, D)
    k,  # (B, Hkv, Tk, D)
    v,  # (B, Hkv, Tk, D)
    *,
    lengths=None,
    causal: bool = True,
    scale: float | None = None,
    blk_q: int = 128,
    blk_k: int = 128,
    technique: str = "gss",
    workers: int = 4,
    chunk: int = 1,
    interpret: bool | None = None,
    costs=None,
    schedule: DeviceSchedule | None = None,
):
    """Self-scheduled attention; returns ``(out, DeviceSchedule)``.

    ``lengths`` (B,) caps each batch row's kv extent (default: full Tk).
    ``costs`` defaults to the varlen kv-block count per tile; pass
    ``schedule`` to reuse a previous claim run on the same tile space.
    """
    from repro.kernels import resolve_interpret

    interpret = resolve_interpret(interpret)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = (D ** -0.5) if scale is None else scale
    nq = -(-Tq // blk_q)

    if lengths is None:
        lengths = np.full(B, Tk, np.int32)
    lengths = np.asarray(lengths, np.int32)
    if lengths.shape != (B,):
        raise ValueError(f"lengths must have shape ({B},), got {lengths.shape}")

    N = B * H * nq
    if schedule is None:
        if costs is None:
            with tracing.span("tile_costs"):
                costs = varlen_tile_costs(lengths, H, nq, blk_q, blk_k,
                                          causal)
        schedule = claim_schedule(
            technique, N, workers, chunk=chunk, costs=costs,
            interpret=interpret)
    if schedule.N != N or schedule.P != workers:
        raise ValueError(
            f"schedule is for (N={schedule.N}, P={schedule.P}), "
            f"this tile space needs (N={N}, P={workers})")
    tables = schedule.launch_tables()
    with tracing.launch("compute.launch", persistent_call):
        out = persistent_call(
            *tables, lengths, q, k, v, causal=causal, scale=float(scale),
            blk_q=blk_q, blk_k=blk_k, interpret=interpret)
    return out, schedule
