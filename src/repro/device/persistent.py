"""The persistent protocol kernel: Step 1-3 of the paper inside Pallas.

One ``pallas_call`` launch owns the whole scheduling loop.  The window
counters arrive as an input/output-aliased int32 slab (the device window
itself -- never copied, handed back mutated), and the kernel repeats the
paper's protocol until the loop drains:

  Step 1  fetch-add the step counter ``i``     (slab RMW)
  Step 2  K'_i from the on-device closed form  (device/chunk_calculus.py)
  Step 3  fetch-add the loop pointer ``lp``    (slab RMW)
  ...     truncate into [0, N), append (i, worker, start, size) to the
          schedule output.

Worker assignment: a fixed fleet of ``P`` program instances is modeled by
per-worker virtual clocks held in the kernel -- each claim goes to the
worker with the minimum accumulated cost (ties to the lowest index), and
that worker's clock advances by the chunk's cost (a prefix-sum lookup
over the caller's per-tile cost model).  This is exactly "the next claim
is taken by the earliest-free block": on sequentially-executed grids
(TPU cores, interpret mode) it is the deterministic realization of the
concurrent protocol, byte-stable for CI, and the emitted schedule is what
the persistent *compute* kernels (kernels/*/persistent.py) then execute
with real parallel programs.

Chunk-sequence parity with the host ``plan()`` is pinned index-for-index
(tests/test_device.py): same technique, same (N, P, chunk) => same
(start, size) sequence, summing exactly to N.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np

from repro import tracing
from repro.core.chunk_calculus import max_steps_bound

from .chunk_calculus import chunk_size_device, host_spec, plan_claims


def _protocol_kernel(
    ctr_in,      # (cap,) int32 SMEM -- the device window slab (aliased)
    csum_ref,    # (N+1,) f32   SMEM -- prefix sum of per-iteration costs
    ctr_out,     # (cap,) int32 SMEM -- aliased output (the same slab)
    steps_ref,   # (S,) int32   SMEM -- schedule rows: protocol step,
    workers_ref,  # (S,) int32  SMEM --   granted worker (-1: no grant),
    starts_ref,  # (S,) int32   SMEM --   first iteration,
    sizes_ref,   # (S,) int32   SMEM --   iterations
    clocks_ref,  # (P,) f32     SMEM -- per-worker virtual busy clocks
    counts_ref,  # (P,) int32   SMEM -- per-worker (per-block) claim counts
    *,
    technique: str,
    N: int,
    P: int,
    chunk: int,
    max_chunk: Optional[int],
    S: int,
    cap: int,
    i_slot: int,
    lp_slot: int,
):
    """Every operand is a scalar table in SMEM: the loop reads and writes
    one counter, clock or schedule cell at a time, which the TPU only
    allows in scalar memory (VMEM takes vector stores)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.loop(0, cap)
    def _copy_slab(j):
        ctr_out[j] = ctr_in[j]

    @pl.loop(0, S)
    def _clear_row(s):  # rows past the last grant stay unread
        workers_ref[s] = jnp.int32(-1)

    @pl.loop(0, P)
    def _clear_worker(w):
        clocks_ref[w] = jnp.float32(0.0)
        counts_ref[w] = jnp.int32(0)

    def earliest_free(w, best):
        # argmin over the clocks, ties to the lowest index
        return jnp.where(clocks_ref[w] < clocks_ref[best], w, best)

    def step(s, carry):
        lp = ctr_out[lp_slot]

        @pl.when(lp < N)
        def _claim():
            i = ctr_out[i_slot]          # Step 1: fetch...
            ctr_out[i_slot] = i + 1      # ...add
            # Step 2 (local): i < 2*S here (resumed loops start past 0),
            # so the GSS double-float power unrolls only that many bits
            k = chunk_size_device(technique, i, N=N, P=P, chunk=chunk,
                                  max_chunk=max_chunk,
                                  i_bits=(2 * S).bit_length())
            start = ctr_out[lp_slot]     # Step 3: fetch...
            ctr_out[lp_slot] = start + k  # ...add

            @pl.when(start < N)
            def _grant():
                size = jnp.minimum(k, N - start)
                w = jax.lax.fori_loop(1, P, earliest_free, jnp.int32(0))
                cost = csum_ref[start + size] - csum_ref[start]
                clocks_ref[w] = clocks_ref[w] + cost
                counts_ref[w] = counts_ref[w] + 1
                steps_ref[s] = i
                workers_ref[s] = w
                starts_ref[s] = start
                sizes_ref[s] = size

        return carry

    jax.lax.fori_loop(0, S, step, 0)


def _protocol_outputs(slab, csum, *, technique: str, N: int, P: int,
                      chunk: int, max_chunk: Optional[int], S: int,
                      i_slot: int, lp_slot: int, interpret: bool):
    """The protocol kernel's ``pallas_call``: ``(slab, steps, workers,
    starts, sizes, clocks, counts)``, the slab aliased in place."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cap = int(slab.shape[0])
    kern = functools.partial(
        _protocol_kernel, technique=technique, N=N, P=P, chunk=chunk,
        max_chunk=max_chunk, S=S, cap=cap, i_slot=i_slot, lp_slot=lp_slot)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    i32, f32 = jnp.int32, jnp.float32
    shapes = [(cap, i32), (S, i32), (S, i32), (S, i32), (S, i32),
              (P, f32), (P, i32)]
    return pl.pallas_call(
        kern,
        in_specs=[smem, smem],
        out_specs=[smem] * len(shapes),
        out_shape=[jax.ShapeDtypeStruct((n,), dt) for n, dt in shapes],
        input_output_aliases={0: 0},
        interpret=interpret,
        name="dls_protocol",
    )(slab, csum)


def _worker_tables(workers, starts, sizes, counts, *, P: int, C: int):
    """The per-worker claim tables of a schedule, built on the device.

    Returns ``(nclaims (P,), starts (P, C), sizes (P, C))`` int32: worker
    ``w``'s first ``nclaims[w]`` entries are its grants in protocol order,
    the rest zero-sized.  Each granted row's place in the flat table is
    ``w * C + rank``, its rank among ``w``'s rows a cumulative sum of the
    one-hot worker column; each empty place gets a zero-sized filler.  A
    sort on the place puts all of them in table order: no scatter, and no
    gather.  Rows that have no place (no grant, or past ``C``) and the
    fillers of taken places sort to the end, which is cut off.
    """
    import jax.numpy as jnp

    S = workers.shape[0]
    mine = workers[:, None] == jnp.arange(P, dtype=jnp.int32)  # (S, P)
    rank = jnp.sum(jnp.where(mine, jnp.cumsum(mine, axis=0), 0), axis=1) - 1
    off = P * C + jnp.arange(S, dtype=jnp.int32)
    place = jnp.where((workers >= 0) & (rank < C), workers * C + rank, off)
    slot = jnp.arange(P * C, dtype=jnp.int32).reshape(P, C)
    fill = jnp.where(slot % C >= counts[:, None], slot,
                     P * C + S + slot).reshape(-1)
    zeros = jnp.zeros(P * C, jnp.int32)
    _, t_starts, t_sizes = jax.lax.sort(
        (jnp.concatenate([place, fill]), jnp.concatenate([starts, zeros]),
         jnp.concatenate([sizes, zeros])), num_keys=1)
    return (jnp.minimum(counts, C), t_starts[:P * C].reshape(P, C),
            t_sizes[:P * C].reshape(P, C))


@functools.partial(jax.jit, static_argnames=(
    "technique", "N", "P", "chunk", "max_chunk", "S", "i_slot", "lp_slot",
    "interpret"))
def protocol_call(slab, csum, *, technique: str, N: int, P: int,
                  chunk: int, max_chunk: Optional[int], S: int,
                  i_slot: int, lp_slot: int, interpret: bool):
    """The protocol kernel, jitted: arrays in and out.

    ``slab`` (cap,) int32 and ``csum`` (N+1,) f32 are device arrays;
    every other argument is static.  Returns ``(slab, packed, tables)``:

    * the slab, aliased in place;
    * the schedule as one int32 vector of length ``4*S + 2*P``, packed so
      that the host reads it back in one transfer::

          steps (S) | workers (S) | starts (S) | sizes (S) | counts (P) | clocks (P)

      ``clocks`` holds the float32 clocks' bits (``bitcast_convert_type``,
      exact);
    * the per-worker claim tables the compute kernels take,
      ``(nclaims (P,), starts (P, C), sizes (P, C))`` (``_worker_tables``),
      ``C = min(plan_claims(...), S)``.

    The tables are built in this module, so the schedule reaches the
    compute kernel without passing through the host.  ``claim_schedule``
    is the host wrapper around this call.
    """
    import jax.numpy as jnp

    slab, steps, workers, starts, sizes, clocks, counts = _protocol_outputs(
        slab, csum, technique=technique, N=N, P=P, chunk=chunk,
        max_chunk=max_chunk, S=S, i_slot=i_slot, lp_slot=lp_slot,
        interpret=interpret)
    C = min(plan_claims(technique, N, P, chunk, max_chunk), S)
    packed = jnp.concatenate([
        steps, workers, starts, sizes, counts,
        jax.lax.bitcast_convert_type(clocks, jnp.int32)])
    return slab, packed, _worker_tables(workers, starts, sizes, counts,
                                        P=P, C=C)


def _host_view(name: str, doc: str):
    return property(lambda self: self._host[name], doc=doc)


class DeviceSchedule:
    """A device-made schedule: its tables on the device, its rows on demand.

    ``tables`` are the per-worker claim tables the compute kernels take
    (``protocol_call``), and ``slab`` the window slab *after* the kernel
    ran (adopt it back into the window); both stay on the device.

    The host views -- ``steps/workers/starts/sizes``, the granted claims
    in protocol order, and the per-block claim counts and modeled busy
    clocks ``counts``/``clocks`` the report plane surfaces -- are slices
    of the packed vector, read back (``repro.claim.readback``) on first
    access and kept.  The copy starts when the claim kernel is launched,
    so a read after the compute kernel finds it landed.
    """

    def __init__(self, technique: str, N: int, P: int, chunk: int, *,
                 packed, tables, slab):
        self.technique, self.N, self.P, self.chunk = technique, N, P, chunk
        self.packed = packed  # jnp (4*S + 2*P,) int32
        self.tables = tables  # jnp (P,), (P, C), (P, C) int32
        self.slab = slab      # jnp (cap,) int32 -- final window counters
        #: whether a compute kernel was handed ``tables`` (``launch_tables``)
        self.launched = False

    def launch_tables(self):
        """``tables``, for a compute kernel about to launch on them.

        The span ``repro.tables`` marks the hand-over, with the tables'
        width as its counter ``width``: the protocol module built them on
        the device, so the host builds and uploads nothing here.  A host
        view first read after this counts ``after_launch``.
        """
        with tracing.span("tables") as span:
            if tracing.enabled():
                span.set_metadata(width=int(self.tables[1].shape[1]))
            self.launched = True
            return self.tables

    @functools.cached_property
    def _host(self) -> dict:
        P, C = self.P, self.tables[1].shape[1]
        S = (self.packed.shape[0] - 2 * P) // 4
        with tracing.span("claim.readback") as readback:
            host = np.asarray(self.packed)  # the one device-to-host transfer
            if tracing.enabled():
                readback.set_metadata(arrays=1, bytes=self.packed.nbytes,
                                      after_launch=int(self.launched))
        steps, workers, starts, sizes = host[:4 * S].reshape(4, S)
        counts, clocks = host[4 * S:].reshape(2, P)
        if counts.max(initial=0) > C:
            raise RuntimeError(
                f"a worker took {counts.max()} claims, more than the "
                f"{C} of the plan: the window's counters were not left by "
                "this loop's protocol, and its tables dropped claims")
        n = int((workers >= 0).sum())  # granted rows form a prefix
        return dict(steps=steps[:n], workers=workers[:n], starts=starts[:n],
                    sizes=sizes[:n], counts=counts.astype(np.int64),
                    clocks=clocks.view(np.float32))

    steps = _host_view("steps", "(n_steps,) int32 protocol step of each grant")
    workers = _host_view("workers", "(n_steps,) int32 granted worker")
    starts = _host_view("starts", "(n_steps,) int32 first iteration")
    sizes = _host_view("sizes", "(n_steps,) int32 iterations")
    counts = _host_view("counts", "(P,) int64 per-worker claim counts")
    clocks = _host_view("clocks", "(P,) float32 modeled busy time")

    @property
    def n_steps(self) -> int:
        return len(self.sizes)

    @property
    def n_rmw(self) -> int:
        """Protocol RMWs the kernel paid (two fetch-adds per step)."""
        return 2 * self.n_steps

    def makespan(self) -> float:
        """Modeled finish time of the busiest worker."""
        return float(self.clocks.max()) if len(self.clocks) else 0.0


def claim_schedule(
    technique: str,
    N: int,
    P: int,
    *,
    chunk: int = 1,
    max_chunk: Optional[int] = None,
    costs=None,
    slab=None,
    i_slot: int = 0,
    lp_slot: int = 1,
    max_steps: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> DeviceSchedule:
    """Run the in-kernel claim loop over ``[0, N)`` with ``P`` workers.

    ``costs`` is the per-iteration cost model (length N; uniform when
    None) driving the earliest-free-worker assignment; ``slab`` is a
    device window slab whose ``i_slot``/``lp_slot`` counters seed the
    protocol (fresh zeros when None -- note nonzero counters resume a
    partially-drained loop, exactly like the host runtime).  Runs under
    the Pallas interpreter on CPU (``kernels.resolve_interpret``).

    Returns without waiting for the device: the claim tables stay there
    for the compute kernel, and the packed schedule's copy to the host is
    started, to be read when a host view is first asked for.
    """
    import jax.numpy as jnp

    from repro.kernels import resolve_interpret

    with tracing.span("claim") as claim:
        interpret = resolve_interpret(interpret)
        spec = host_spec(technique, N, P, chunk, max_chunk)
        S = int(max_steps or max_steps_bound(spec))
        with tracing.span("claim.costs"):
            if costs is None:
                costs = np.ones(N, np.float32)
            costs = np.asarray(costs, np.float64)
            if costs.shape != (N,):
                raise ValueError(
                    f"costs must have shape ({N},), got {costs.shape}")
            csum = np.zeros(N + 1, np.float32)
            np.cumsum(costs, out=csum[1:])
            if slab is None:
                slab = jnp.zeros(max(i_slot, lp_slot) + 1, jnp.int32)
            csum = jnp.asarray(csum)
        cap = int(slab.shape[0])
        if not (0 <= i_slot < cap and 0 <= lp_slot < cap
                and i_slot != lp_slot):
            raise ValueError(f"bad counter slots ({i_slot}, {lp_slot}) "
                             f"for slab of capacity {cap}")

        with tracing.launch("claim.launch", protocol_call):
            new_slab, packed, tables = protocol_call(
                slab, csum, technique=technique, N=N, P=P, chunk=chunk,
                max_chunk=max_chunk, S=S, i_slot=i_slot, lp_slot=lp_slot,
                interpret=interpret)
        packed.copy_to_host_async()
        if tracing.enabled():
            # the tables' width is the plan's claim count: exact for a
            # loop claimed from its start, with no read-back (a resumed
            # loop grants fewer, which ``n_steps`` gives once read)
            claim.set_metadata(steps=S, claims=int(tables[1].shape[1]))
    return DeviceSchedule(technique, N, P, chunk, packed=packed,
                          tables=tables, slab=new_slab)


def schedule_timeline(schedule: DeviceSchedule, costs=None):
    """Per-claim (t0, t1) under the earliest-free-worker model.

    Recomputes the kernel's clock walk on the host (same csum, same
    order => same numbers) so executors can emit ``chunk_times`` rows
    without shipping timestamps out of the kernel: a worker's clock is
    the float32 running sum of its claims' costs in grant order, which
    ``np.cumsum`` along a worker's row adds one claim at a time, as the
    kernel does.
    """
    N, P = schedule.N, schedule.P
    if costs is None:
        costs = np.ones(N, np.float64)
    csum = np.zeros(N + 1, np.float32)
    np.cumsum(np.asarray(costs, np.float64), out=csum[1:])
    workers, starts = schedule.workers, schedule.starts
    # each claim's place in its worker's grant order, from 1
    counts = np.bincount(workers, minlength=P)
    order = np.argsort(workers, kind="stable")
    place = np.empty_like(order)
    place[order] = (np.arange(1, len(order) + 1)
                    - np.repeat(np.cumsum(counts) - counts, counts))
    clocks = np.zeros((P, counts.max(initial=0) + 1), np.float32)
    clocks[workers, place] = csum[starts + schedule.sizes] - csum[starts]
    clocks = np.cumsum(clocks, axis=1, dtype=np.float32)
    return (clocks[workers, place - 1].astype(np.float64),
            clocks[workers, place].astype(np.float64))
