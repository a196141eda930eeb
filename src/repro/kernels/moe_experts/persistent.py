"""Persistent self-scheduled routed experts: one chip's share of a MoE layer.

A chip that holds ``E`` of a layer's routed experts (expert parallelism)
computes, for the tokens routed to them, ``down(silu(x @ Wg) * (x @ Wu))``
scaled by each pair's routing weight, and sums each token's pairs.  How
many rows an expert gets is known only once the router has run, and real
routing is skewed, so the expert tiles are the variable-cost loop the
claim protocol (``repro.device``, DESIGN.md Sec. 14) balances:

  * tile ``t = e * R + r`` covers rows ``[r * blk, (r + 1) * blk)`` of held
    expert ``e``'s routed rows, ``R = ceil(T / blk)``.  A token picks an
    expert at most once, so ``N = E * R`` tiles hold any routing: nothing
    is dropped.  A tile's cost is its row count, 0 past an expert's load.
  * ``route_pairs`` sorts the routed (token, held expert) pairs by expert;
    the per-expert row counts are read back once, and set the tile costs,
    the claims and the rows gathered.
  * the expert kernel streams from HBM: a fixed fleet of ``workers``
    programs lists the live tiles of its claims, then for each one fetches
    the tile's gathered rows and the expert's weights in blocks of ``TF``
    intermediate columns, double-buffered, with bf16 operands and float32
    accumulation.  A tile with no rows costs neither a DMA nor a matmul.
    One expert's weights (88 MB at DeepSeek-V3's widths) do not fit in
    VMEM, so nothing is a whole-array block.
  * the combine kernel sums each token's weighted rows block by block of
    tokens: the rows of one expert for one block of tokens lie together,
    so each is fetched in a few aligned windows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing
from repro.device.persistent import claim_schedule

#: intermediate columns per weight block: three (d, 512) bf16 blocks at
#: d = 7168 are 22 MB, about as long to fetch as their matmuls take on a
#: 256-row tile, so the fetch of the next block hides behind this one's
TF = 512
#: output columns per down-projection matmul, to bound its f32 temporary
TD = 1024
#: tokens per block of the combine, and rows per window it fetches
TB, WIN = 256, 32


def _experts_kernel(
    nclaims_ref,  # (W,)   int32 SMEM -- claims per worker
    starts_ref,   # (W*C,) int32 SMEM -- first tile of each claim
    sizes_ref,    # (W*C,) int32 SMEM -- tiles in each claim
    loads_ref,    # (E,)   int32 SMEM -- routed rows per held expert
    offs_ref,     # (E,)   int32 SMEM -- first row of each expert in xs
    xs_hbm,       # (M, d)    HBM -- routed rows, sorted by expert
    pw_hbm,       # (M, 128)  HBM -- their routing weights, f32, lane-wide
    wg_hbm,       # (E, d, F) HBM
    wu_hbm,       # (E, d, F) HBM
    wd_hbm,       # (E, F, d) HBM
    ys_hbm,       # (M + WIN, d) HBM -- weighted output rows, f32
    tiles,        # (N,) int32 SMEM scratch -- this worker's live tiles
    xbuf,         # (2, blk, d) VMEM -- rows of this tile and the next
    wbuf,         # (2, blk, 128) VMEM -- and their weights
    gbuf,         # (2, d, tf)  VMEM -- weight blocks, double-buffered
    ubuf,         # (2, d, tf)
    dbuf,         # (2, tf, d)
    acc,          # (blk, d) f32 VMEM
    ybuf,         # (blk, d) f32 VMEM -- the finished tile on its way out
    sems,         # DMA semaphores (6, 2): rows, g, u, d by slot; out; w
    *,
    R: int,
    blk: int,
    tf: int,
    nf: int,
    C: int,
):
    w = pl.program_id(0)
    d = acc.shape[1]
    td = min(TD, d)

    # the live tiles of this worker's claims, in claim order
    def claim_body(c, n):
        st = starts_ref[w * C + c]
        end = st + sizes_ref[w * C + c]

        def expert_body(e, n):
            live = (loads_ref[e] + blk - 1) // blk
            lo = jnp.maximum(st, e * R)
            hi = jnp.minimum(end, e * R + live)

            def put(t, n):
                tiles[n] = t
                return n + 1

            return jax.lax.fori_loop(lo, hi, put, n)

        return jax.lax.fori_loop(st // R, (end - 1) // R + 1, expert_body, n)

    n = jax.lax.fori_loop(0, nclaims_ref[w], claim_body, 0)
    steps = n * nf  # one step: one weight block of one live tile

    def row_of(j):
        t = tiles[j]
        e = t // R
        return e, pl.multiple_of(offs_ref[e] + (t - e * R) * blk, blk)

    def copies(s):
        j = s // nf
        f = s - j * nf
        e, row = row_of(j)
        col = pl.multiple_of(f * tf, tf)
        slot, xslot = s % 2, j % 2
        rows = (pltpu.make_async_copy(xs_hbm.at[pl.ds(row, blk)],
                                      xbuf.at[xslot], sems.at[0, xslot]),
                pltpu.make_async_copy(pw_hbm.at[pl.ds(row, blk)],
                                      wbuf.at[xslot], sems.at[5, xslot]))
        ws = (pltpu.make_async_copy(wg_hbm.at[e, :, pl.ds(col, tf)],
                                    gbuf.at[slot], sems.at[1, slot]),
              pltpu.make_async_copy(wu_hbm.at[e, :, pl.ds(col, tf)],
                                    ubuf.at[slot], sems.at[2, slot]),
              pltpu.make_async_copy(wd_hbm.at[e, pl.ds(col, tf), :],
                                    dbuf.at[slot], sems.at[3, slot]))
        return f, rows, ws

    def out_copy(j):
        return pltpu.make_async_copy(ybuf, ys_hbm.at[pl.ds(row_of(j)[1], blk)],
                                     sems.at[4, 0])

    def start(s):
        f, rows, ws = copies(s)

        @pl.when(f == 0)
        def _():
            for c in rows:
                c.start()

        for c in ws:
            c.start()

    @pl.when(steps > 0)
    def _():
        start(0)

    def step(s, carry):
        @pl.when(s + 1 < steps)
        def _():
            start(s + 1)

        f, rows, ws = copies(s)
        j = s // nf
        slot, xslot = s % 2, j % 2

        @pl.when(f == 0)
        def _():
            for c in rows:
                c.wait()

        for c in ws:
            c.wait()
        xt = xbuf[xslot]
        g = jnp.dot(xt, gbuf[slot], preferred_element_type=jnp.float32)
        u = jnp.dot(xt, ubuf[slot], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(dbuf.dtype)
        for c0 in range(0, d, td):
            part = jnp.dot(h, dbuf[slot, :, c0:c0 + td],
                           preferred_element_type=jnp.float32)
            acc[:, c0:c0 + td] = jnp.where(f == 0, 0.0,
                                           acc[:, c0:c0 + td]) + part

        @pl.when(f == nf - 1)
        def _():
            @pl.when(j > 0)
            def _():
                out_copy(j - 1).wait()  # ybuf is free again

            ybuf[...] = acc[...] * wbuf[xslot][:, :1]
            out_copy(j).start()

        return carry

    jax.lax.fori_loop(0, steps, step, 0)

    @pl.when(n > 0)
    def _():
        out_copy(n - 1).wait()


def _combine_kernel(
    bounds_ref,   # (E*(nb+1),) int32 SMEM -- expert e's rows below block b
    offs_ref,     # (E,)        int32 SMEM -- first row of each expert
    loc_ref,      # (E, TB)     int32 SMEM -- token in the block of each row
    ys_hbm,       # (M + WIN, d) f32 HBM -- weighted rows, sorted by expert
    y_ref,        # (TB, d) -- this block of the output
    acc,          # (TB, d) f32 VMEM
    buf,          # (E, WIN, d) f32 VMEM -- a window of rows per expert
    sems,         # DMA semaphores (E,)
    *,
    E: int,
    nb: int,
):
    """One block of TB tokens: for each held expert, the rows of this
    block's tokens are ``[lo, lo + n)`` of ``ys``, in token order; they are
    fetched in WIN-row windows from the aligned row at or below ``lo``
    and added to their tokens' rows of a float32 accumulator."""
    b = pl.program_id(0)
    acc[...] = jnp.zeros_like(acc)

    def span(e):
        below = bounds_ref[e * (nb + 1) + b]
        lo = offs_ref[e] + below
        return lo, bounds_ref[e * (nb + 1) + b + 1] - below, lo // 8 * 8

    def window(e, at):
        return pltpu.make_async_copy(ys_hbm.at[pl.ds(pl.multiple_of(at, 8),
                                                     WIN)],
                                     buf.at[e], sems.at[e])

    for e in range(E):  # every expert's first window in flight at once
        lo, n, base = span(e)

        @pl.when(n > 0)
        def _():
            window(e, base).start()

    for e in range(E):
        lo, n, base = span(e)

        def add_window(k, carry, e=e, lo=lo, n=n, base=base):
            at = base + k * WIN

            @pl.when(k > 0)
            def _():
                window(e, at).start()

            window(e, at).wait()

            def add(j, c):
                i = loc_ref[e, j]
                acc[pl.ds(i, 1), :] += buf[e, pl.ds(lo + j - at, 1), :]
                return c

            return jax.lax.fori_loop(jnp.maximum(at - lo, 0),
                                     jnp.minimum(at + WIN - lo, n), add,
                                     carry)

        jax.lax.fori_loop(0, windows(lo, n), add_window, 0)
    y_ref[...] = acc[...].astype(y_ref.dtype)


def windows(lo, n):
    """WIN-row windows that cover rows ``[lo, lo + n)`` from the 8-aligned
    row at or below ``lo``; none for no rows (a window counted but never
    fetched would be waited for forever)."""
    return jnp.where(n > 0, (lo % 8 + n + WIN - 1) // WIN, 0)


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM buffer: the minor dim fills 128 lanes."""
    *lead, minor = shape
    return int(np.prod(lead)) * -(-minor // 128) * 128 * \
        jnp.dtype(dtype).itemsize


def _vmem_scratch(blk: int, d: int, tf: int, dt):
    """(shape, dtype) of the expert kernel's VMEM buffers: rows and
    weights of two tiles, two of each weight block, the f32 accumulator,
    the outgoing tile."""
    f32 = jnp.float32
    return [((2, blk, d), dt), ((2, blk, 128), f32), ((2, d, tf), dt),
            ((2, d, tf), dt), ((2, tf, d), dt), ((blk, d), f32),
            ((blk, d), f32)]


def vmem_limit(blk: int, d: int, F: int, dtype) -> int:
    """VMEM the expert kernel asks of the compiler: its buffers, and 16 MiB
    for the f32 temporaries of one step (g, u, h, one down-projection
    part)."""
    scratch = _vmem_scratch(blk, d, min(TF, F), dtype)
    return sum(_vmem_bytes(s, t) for s, t in scratch) + (16 << 20)


def _offsets(loads, blk: int):
    """First row of each expert's rows: each starts on a multiple of blk."""
    padded = (loads + blk - 1) // blk * blk
    return jnp.cumsum(padded) - padded


@functools.partial(jax.jit, static_argnames=("held",))
def route_pairs(expert_ids, expert_w, *, held: tuple):
    """The routed pairs that land on the ``held`` experts, by expert.

    ``expert_ids``/``expert_w`` (T, K) are the router's choices over all
    experts.  Returns ``(loads (E,), tok (E, T), w (E, T), loc (E, nb *
    TB), bounds (E, nb + 1))``, ``nb = ceil(T / TB)``, int32 but ``w``:
    ``tok[e, :loads[e]]`` are the tokens routed to held expert ``e`` in
    order and ``w`` their weights; ``loc[e]`` holds, block of TB tokens
    by block, the places in the block of the tokens routed to ``e``, in
    order; ``bounds[e, b]`` counts those below block ``b``.  Sorts,
    cumulative sums and comparisons only: a scatter of the pairs costs ms
    on the chip.
    """
    T, _ = expert_ids.shape
    E = len(held)
    hits = expert_ids[None] == jnp.asarray(held, jnp.int32)[:, None, None]
    mask = hits.any(-1)                                    # (E, T)
    w = jnp.where(hits, expert_w[None].astype(jnp.float32), 0.0).sum(-1)
    t = jnp.arange(T, dtype=jnp.int32)
    tok, w = jax.lax.sort((jnp.where(mask, t, T + t), w), num_keys=1)
    blocks = jnp.pad(mask, ((0, 0), (0, -T % TB))).reshape(E, -1, TB)
    i = jnp.arange(TB, dtype=jnp.int32)
    loc = jnp.sort(jnp.where(blocks, i, TB + i), axis=-1)
    per_block = blocks.sum(-1, dtype=jnp.int32)
    bounds = jnp.pad(jnp.cumsum(per_block, axis=1), ((0, 0), (1, 0)))
    return (mask.sum(1, dtype=jnp.int32), tok, w, loc.reshape(E, -1),
            bounds)


def expert_tile_costs(loads, T: int, blk: int) -> np.ndarray:
    """Rows of each tile ``e * R + r``: ``blk`` up to the expert's load,
    its remainder in the last live tile, 0 past it."""
    R = -(-T // blk)
    r = np.arange(R) * blk
    loads = np.asarray(loads, np.int64)[:, None]
    return np.clip(loads - r[None, :], 0, blk).ravel().astype(np.float64)


def rows_bucket(loads, blk: int) -> int:
    """Rows to gather: every live tile's ``blk``, rounded up to a multiple
    of 8 tiles so that a few compiled shapes serve every routing."""
    live = int(sum(-(-int(n) // blk) for n in loads))
    return max(-(-live // 8) * 8, 8) * blk


@functools.partial(jax.jit, static_argnames=("M", "blk", "interpret"))
def persistent_call(nclaims, starts, sizes, loads, tok, w, loc, bounds, x,
                    w_gate, w_up, w_down, *, M: int, blk: int,
                    interpret: bool):
    """Gather, the streaming expert kernel and the combine kernel.

    ``nclaims (W,)``, ``starts``/``sizes (W, C)`` are the per-worker claim
    tables and ``loads (E,)`` the routed rows per held expert, all int32;
    they ride in SMEM as scalar-prefetch operands, as ``bounds`` does in
    the combine.  ``tok``, ``w``, ``loc``, ``bounds`` are
    ``route_pairs``'.  ``M`` rows are gathered (``rows_bucket``).  x (T,
    d) and the weights stay in HBM and the expert kernel fetches what
    each claimed tile needs.  Returns ``(T, d)`` in x's dtype: each
    token's weighted sum over its held experts, 0 for a token routed to
    none of them.
    """
    T, d = x.shape
    E, _, F = w_gate.shape
    W, C = starts.shape
    R = -(-T // blk)
    tf = min(TF, F)
    assert F % tf == 0, (F, tf)
    offs = _offsets(loads, blk)

    # row q of the gathered rows is held expert e's token of rank
    # q - offs[e]; rows past an expert's load read token 0, weight 0
    q = jnp.arange(M, dtype=jnp.int32)
    e_q = (q[:, None] >= offs[None, 1:]).sum(1)
    r_q = jnp.minimum(q - offs[e_q], T - 1)
    live = q - offs[e_q] < loads[e_q]
    xs = jnp.take(x, jnp.where(live, tok[e_q, r_q], 0), axis=0,
                  mode="clip")
    # a DMA moves whole 128-lane rows: the weights are spread across them
    pw = jnp.broadcast_to(jnp.where(live, w[e_q, r_q], 0.0)[:, None],
                          (M, 128))

    dt = x.dtype
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    ys = pl.pallas_call(
        functools.partial(_experts_kernel, R=R, blk=blk, tf=tf, nf=F // tf,
                          C=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(W,),
            in_specs=[any_] * 5,
            out_specs=any_,
            scratch_shapes=[pltpu.SMEM((E * R,), jnp.int32)]
            + [pltpu.VMEM(s, t) for s, t in _vmem_scratch(blk, d, tf, dt)]
            + [pltpu.SemaphoreType.DMA((6, 2))],
        ),
        # WIN rows past the last, for the combine's last window to read
        out_shape=jax.ShapeDtypeStruct((M + WIN, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(blk, d, F, dt)),
        interpret=interpret,
        name="moe_experts_persistent",
    )(nclaims, starts.reshape(-1), sizes.reshape(-1), loads, offs, xs, pw,
      w_gate, w_up, w_down)

    nb = -(-T // TB)
    return pl.pallas_call(
        functools.partial(_combine_kernel, E=E, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[pl.BlockSpec((E, TB), lambda b, *_: (0, b),
                                   memory_space=pltpu.SMEM), any_],
            out_specs=pl.BlockSpec((TB, d), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((TB, d), jnp.float32),
                            pltpu.VMEM((E, WIN, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((E,))],
        ),
        out_shape=jax.ShapeDtypeStruct((nb * TB, d), dt),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_bytes((TB, d), jnp.float32)
            + _vmem_bytes((E, WIN, d), jnp.float32)
            + 2 * _vmem_bytes((TB, d), dt) + (4 << 20)),
        interpret=interpret,
        name="moe_combine",
    )(bounds.reshape(-1), offs, loc, ys)[:T]


def moe_experts_persistent(x, w_gate, w_up, w_down, expert_ids, expert_w, *,
                           held, technique: str = "gss", workers: int = 8,
                           blk: int = 256, interpret: bool | None = None):
    """Self-scheduled routed experts of one chip's share; returns
    ``(y, DeviceSchedule)``.

    ``x`` (T, d); ``w_gate``/``w_up`` (E, d, F) and
    ``w_down`` (E, F, d) are the weights of the ``E`` experts this chip
    holds, whose global ids are ``held``; ``expert_ids``/``expert_w`` (T,
    K) are the router's choices and weights over all experts.  ``y`` (T,
    d) is the held experts' part of the layer's output: dropless,
    whatever the routing.
    """
    from repro.kernels import resolve_interpret

    interpret = resolve_interpret(interpret)
    held = tuple(int(e) for e in held)
    T, _ = x.shape
    E = len(held)
    if w_gate.shape[0] != E:
        raise ValueError(f"weights of {w_gate.shape[0]} experts for "
                         f"{E} held ids")
    R = -(-T // blk)
    with tracing.span("route"):
        loads_dev, *pairs = route_pairs(expert_ids, expert_w, held=held)
        with tracing.span("route.readback") as readback:
            loads = np.asarray(loads_dev)  # the one read-back of a drain
            if tracing.enabled():
                readback.set_metadata(
                    bytes=loads.nbytes, pairs=int(loads.sum()),
                    max_load=int(loads.max()),
                    live_tiles=int((-(-loads // blk)).sum()))
    with tracing.span("tile_costs"):
        costs = expert_tile_costs(loads, T, blk)
    schedule = claim_schedule(technique, E * R, workers, costs=costs,
                              interpret=interpret)
    tables = schedule.launch_tables()
    with tracing.launch("compute.launch", persistent_call):
        y = persistent_call(*tables, loads_dev, *pairs, x, w_gate, w_up,
                            w_down, M=rows_bucket(loads, blk), blk=blk,
                            interpret=interpret)
    return y, schedule
