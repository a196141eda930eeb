"""On-device chunk calculus: traceable ports of the paper's closed forms.

The distributed protocol's whole premise is that ``K'_i`` is a pure
function of the fetched step index ``i`` (core/chunk_calculus.py).  That
property survives a change of hardware: this module re-expresses the
closed forms in jax so a Pallas kernel block that fetch-adds ``i`` from
the device window can compute its chunk *on the accelerator*, with no
host round trip.

Parity contract (pinned by tests/test_device.py): for every technique
here, ``chunk_size_device(t, idx, ...)`` equals
``core.chunk_calculus.chunk_sizes_closed(host_spec(t, ...), idx)``
index-for-index.  Two numeric traps are designed around:

  * GSS: the host evaluates ``ceil(((P-1)/P)**i * N/P)`` in float64, and
    accelerators only have f32 -- where a plain f32 ``power`` disagrees
    with f64 exactly at integer ceil boundaries (e.g. N=513, P=3, i=2:
    the true value is the integer 76; f32 rounds the power up and ceils
    to 77).  The device form therefore computes the product in
    *double-float* (two-f32 compensated) arithmetic -- Dekker two-product
    and square-and-multiply over the bits of ``i``, ~48 bits of effective
    precision from f32-only ops -- which reproduces the f64 ceil on every
    grid swept (N<=100k, P<=64, plus randomized sweeps in tests).
  * FAC2 avoids floats entirely: ``ceil(0.5**b * N/P)`` is computed as
    nested integer ceil-division ``ceil(ceil(N/P) / 2**b)`` (the two are
    identical for positive integers), with ``b`` clamped so the shift
    never overflows int32 -- past that point the chunk is min_chunk
    anyway.

Techniques: the non-adaptive, non-weighted subset of the host registry
(static/SS/GSS/TSS/FAC2) plus ``fsc`` -- fixed-size chunking with a
caller-chosen K, which is the host's ``ss`` with ``min_chunk=K`` (the
``host_spec`` mapping tests pin against).  Weighted/adaptive techniques
need live telemetry and stay host-side.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.chunk_calculus import LoopSpec, tss_constants

#: Techniques the device kernels implement.  ``fsc`` is device-only
#: naming; everything else matches core.chunk_calculus.TECHNIQUES.
DEVICE_TECHNIQUES = ("static", "ss", "fsc", "gss", "tss", "fac2")


def host_spec(technique: str, N: int, P: int, chunk: int = 1,
              max_chunk: Optional[int] = None) -> LoopSpec:
    """The host ``LoopSpec`` a device schedule must match index-for-index.

    ``fsc`` (fixed-size chunking of K iterations) maps onto the host's
    ``ss`` with ``min_chunk=K``; for every other technique ``chunk`` is
    the host ``min_chunk``.
    """
    if technique not in DEVICE_TECHNIQUES:
        raise ValueError(
            f"technique {technique!r} has no device closed form; "
            f"pick from {DEVICE_TECHNIQUES}")
    t = "ss" if technique == "fsc" else technique
    return LoopSpec(t, N=N, P=P, min_chunk=chunk, max_chunk=max_chunk)


def _two_prod(a, b):
    """Dekker's exact product: a*b == p + err, f32-only (Veltkamp split)."""
    split = jnp.float32(4097.0)  # 2**12 + 1
    p = a * b
    ca = split * a
    a_hi = ca - (ca - a)
    a_lo = a - a_hi
    cb = split * b
    b_hi = cb - (cb - b)
    b_lo = b - b_hi
    err = ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo
    return p, err


def _df_mul(ah, al, bh, bl):
    """Double-float multiply: (ah+al)*(bh+bl) -> renormalized (hi, lo)."""
    p, e = _two_prod(ah, bh)
    e = e + (ah * bl + al * bh)
    hi = p + e
    lo = e - (hi - p)
    return hi, lo


def _gss_geometric_df(i, N: int, P: int, i_bits: int = 31):
    """``((P-1)/P)**i * (N/P)`` in double-float, then a boundary-safe ceil.

    Square-and-multiply over the ``i_bits`` bits of ``i`` keeps ~48 bits
    of effective precision from f32-only ops, so the ceil agrees with
    the host's f64 even when the true value sits exactly on an integer.
    Both constants are split hi/lo on the host in f64.  Callers that
    know a bound on ``i`` (the protocol kernel knows its step budget)
    pass a smaller ``i_bits`` to shorten the unrolled trace.
    """
    q64 = (P - 1.0) / P
    q_hi = np.float32(q64)
    q_lo = np.float32(q64 - np.float64(q_hi))
    np64 = N / P
    n_hi = np.float32(np64)
    n_lo = np.float32(np64 - np.float64(n_hi))

    fi = i.astype(jnp.int32)
    rh = jnp.ones_like(fi, jnp.float32)
    rl = jnp.zeros_like(fi, jnp.float32)
    bh = jnp.full_like(rh, q_hi)
    bl = jnp.full_like(rh, q_lo)
    i_bits = max(1, min(int(i_bits), 31))
    for bit in range(i_bits):
        take = ((fi >> bit) & 1) == 1
        mh, ml = _df_mul(rh, rl, bh, bl)
        rh = jnp.where(take, mh, rh)
        rl = jnp.where(take, ml, rl)
        if bit < i_bits - 1:
            bh, bl = _df_mul(bh, bl, bh, bl)
    vh, vl = _df_mul(rh, rl, jnp.full_like(rh, n_hi), jnp.full_like(rh, n_lo))

    # ceil(vh + vl): vl only matters when vh sits next to an integer, and
    # there (|vh - round(vh)| < 0.25) the small difference is exact in f32.
    near_int = jnp.round(vh)
    d = (vh - near_int) + vl
    near = jnp.abs(vh - near_int) < 0.25
    return jnp.where(near, near_int + (d > 0).astype(jnp.float32),
                     jnp.ceil(vh))


def chunk_size_device(technique: str, i, *, N: int, P: int, chunk: int = 1,
                      max_chunk: Optional[int] = None,
                      i_bits: int = 31):
    """K'_i as a traced int32 (scalar or array) -- Step 2 on the device.

    ``i`` may be a traced scalar (inside the protocol kernel) or an index
    array (vectorized parity checks); every op is elementwise so the same
    expression serves both.  N/P/chunk are static Python ints: the
    technique constants fold into the trace, exactly like the host PE's
    "private copy of the closed form".  ``i_bits`` (GSS only) bounds the
    bit width of ``i`` to shorten the double-float power's unrolled trace
    when the caller knows its step budget.
    """
    if technique not in DEVICE_TECHNIQUES:
        raise ValueError(
            f"technique {technique!r} has no device closed form; "
            f"pick from {DEVICE_TECHNIQUES}")
    i = jnp.asarray(i, jnp.int32)
    mc = jnp.int32(chunk)
    if technique == "static":
        k = jnp.full_like(i, -(-N // P))
    elif technique in ("ss", "fsc"):
        k = jnp.full_like(i, chunk)
    elif technique == "gss":
        # Eq. 1: ceil(((P-1)/P)^i * N/P) in double-float (module docstring).
        g = _gss_geometric_df(i, N, P, i_bits)
        k = jnp.maximum(g.astype(jnp.int32), mc)
    elif technique == "tss":
        # Eq. 2 is integer-exact: K_0 - i*C with host-computed constants.
        K0, Klast, _S, C = tss_constants(N, P, chunk)
        k = jnp.maximum(jnp.int32(K0) - i * jnp.int32(C), jnp.int32(Klast))
    else:  # fac2
        # Eq. 3 via nested integer ceil-division (see module docstring).
        # b is clamped so 1 << b stays in int32; beyond the clamp the
        # halved chunk is <= 1 <= min_chunk for any representable N.
        a = jnp.int32(-(-N // P))  # ceil(N/P)
        b = jnp.minimum(i // jnp.int32(P) + 1, 30)
        k = (a + (jnp.int32(1) << b) - 1) >> b
        k = jnp.maximum(k, mc)
    if max_chunk:
        k = jnp.minimum(k, jnp.int32(max_chunk))
    return k


def max_steps_device(technique: str, N: int, P: int, chunk: int = 1,
                     max_chunk: Optional[int] = None) -> int:
    """Static bound on scheduling steps (sizes the kernel's fori_loop and
    the schedule output buffer) -- the host bound over ``host_spec``."""
    from repro.core.chunk_calculus import max_steps_bound

    return int(max_steps_bound(host_spec(technique, N, P, chunk, max_chunk)))


@functools.lru_cache(maxsize=256)
def plan_claims(technique: str, N: int, P: int, chunk: int = 1,
                max_chunk: Optional[int] = None) -> int:
    """Claims of the technique's closed-form plan over a fresh loop.

    Chunk sizes are closed forms in the step index, so the count does not
    depend on the cost model (only the choice of worker reads costs), and
    a loop resumed from counters the protocol left behind grants the rest
    of the same plan.  No worker can take more claims than this: it is the
    static width of the per-worker claim tables (``protocol_call``).
    """
    from repro.core.chunk_calculus import scheduling_steps

    return scheduling_steps(host_spec(technique, N, P, chunk, max_chunk))


def plan_device(technique: str, N: int, P: int, chunk: int = 1,
                max_chunk: Optional[int] = None):
    """Vectorized device schedule: (sizes, starts, n_valid) int32 jnp arrays.

    The batched realization of the device closed forms (padded, sizes
    truncated into [0, N)) -- the on-device analogue of
    ``core.chunk_calculus.plan`` and the cheap half of the parity pin
    (the expensive half runs the sequential protocol kernel).
    """
    S = max_steps_device(technique, N, P, chunk, max_chunk)
    idx = jnp.arange(S, dtype=jnp.int32)
    sizes = chunk_size_device(technique, idx, N=N, P=P, chunk=chunk,
                              max_chunk=max_chunk)
    csum = jnp.cumsum(sizes)
    prev = csum - sizes  # exclusive prefix = the loop pointer per step
    sizes = jnp.clip(jnp.minimum(sizes, N - prev), 0, None)
    starts = jnp.minimum(prev, N)
    n_valid = jnp.sum((sizes > 0).astype(jnp.int32))
    return sizes, starts, n_valid


def ceil_div(a: int, b: int) -> int:
    """Host-side integer ceil division (shared by the wrappers)."""
    return -(-a // b)
