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

import dataclasses
import functools
from typing import Optional

import jax
import numpy as np

from repro import tracing
from repro.core.chunk_calculus import max_steps_bound

from .chunk_calculus import chunk_size_device, host_spec


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


@functools.partial(jax.jit, static_argnames=(
    "technique", "N", "P", "chunk", "max_chunk", "S", "i_slot", "lp_slot",
    "interpret"))
def protocol_call(slab, csum, *, technique: str, N: int, P: int,
                  chunk: int, max_chunk: Optional[int], S: int,
                  i_slot: int, lp_slot: int, interpret: bool):
    """The protocol kernel, jitted: arrays in and out.

    ``slab`` (cap,) int32 and ``csum`` (N+1,) f32 are device arrays;
    every other argument is static.  Returns ``(slab, packed)``: the slab
    aliased in place, and the schedule as one int32 vector of length
    ``4*S + 2*P``, packed in the same module so that the host reads it
    back in one transfer::

        steps (S) | workers (S) | starts (S) | sizes (S) | counts (P) | clocks (P)

    ``clocks`` holds the float32 clocks' bits (``bitcast_convert_type``,
    exact).  ``claim_schedule`` is the host wrapper around this call.
    """
    import jax.numpy as jnp

    slab, steps, workers, starts, sizes, clocks, counts = _protocol_outputs(
        slab, csum, technique=technique, N=N, P=P, chunk=chunk,
        max_chunk=max_chunk, S=S, i_slot=i_slot, lp_slot=lp_slot,
        interpret=interpret)
    return slab, jnp.concatenate([
        steps, workers, starts, sizes, counts,
        jax.lax.bitcast_convert_type(clocks, jnp.int32)])


@dataclasses.dataclass
class DeviceSchedule:
    """A fully-materialized device-made schedule (+ the mutated slab).

    ``steps/workers/starts/sizes`` are the granted claims in protocol
    order; ``counts``/``clocks`` are the per-block claim counts and
    modeled busy clocks the report plane surfaces; ``slab`` is the
    window slab *after* the kernel ran (adopt it back into the window).
    """

    technique: str
    N: int
    P: int
    chunk: int
    steps: np.ndarray    # (n_steps,) int32
    workers: np.ndarray  # (n_steps,) int32
    starts: np.ndarray   # (n_steps,) int32
    sizes: np.ndarray    # (n_steps,) int32
    counts: np.ndarray   # (P,) int64 per-worker claim counts
    clocks: np.ndarray   # (P,) float modeled busy time
    slab: object         # jnp (cap,) int32 -- final window counters

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

    def worker_lists(self):
        """Padded per-worker claim tables for the compute kernels.

        Returns ``(nclaims (P,), starts (P, C), sizes (P, C))`` int32,
        ``C = max(claims per worker, 1)``; padding rows are zero-sized.
        """
        C = max(int(self.counts.max()) if len(self.counts) else 0, 1)
        nclaims = np.zeros(self.P, np.int32)
        starts = np.zeros((self.P, C), np.int32)
        sizes = np.zeros((self.P, C), np.int32)
        for w, st, sz in zip(self.workers, self.starts, self.sizes):
            c = nclaims[w]
            starts[w, c] = st
            sizes[w, c] = sz
            nclaims[w] = c + 1
        return nclaims, starts, sizes


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

    The schedule comes back in one device-to-host transfer, the packed
    vector of ``protocol_call``; every field is a slice of that one host
    buffer (``clocks`` viewed back as float32), and the slab stays on the
    device.
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
            new_slab, packed = protocol_call(
                slab, csum, technique=technique, N=N, P=P, chunk=chunk,
                max_chunk=max_chunk, S=S, i_slot=i_slot, lp_slot=lp_slot,
                interpret=interpret)

        with tracing.span("claim.readback") as readback:
            host = np.asarray(packed)  # the one device-to-host transfer
            steps, workers, starts, sizes = host[:4 * S].reshape(4, S)
            counts, clocks = host[4 * S:].reshape(2, P)
            n = int((workers >= 0).sum())  # granted rows form a prefix
            sched = DeviceSchedule(
                technique=technique, N=N, P=P, chunk=chunk,
                steps=steps[:n], workers=workers[:n], starts=starts[:n],
                sizes=sizes[:n], counts=counts.astype(np.int64),
                clocks=clocks.view(np.float32), slab=new_slab)
            if tracing.enabled():
                readback.set_metadata(arrays=1, bytes=packed.nbytes)
        if tracing.enabled():
            claim.set_metadata(steps=S, claims=n)
    return sched


def schedule_timeline(schedule: DeviceSchedule, costs=None):
    """Per-claim (t0, t1) under the earliest-free-worker model.

    Recomputes the kernel's clock walk on the host (same csum, same
    order => same numbers) so executors can emit ``chunk_times`` rows
    without shipping timestamps out of the kernel.
    """
    N = schedule.N
    if costs is None:
        costs = np.ones(N, np.float64)
    csum = np.zeros(N + 1, np.float32)
    np.cumsum(np.asarray(costs, np.float64), out=csum[1:])
    clocks = np.zeros(schedule.P, np.float32)
    t0s = np.zeros(schedule.n_steps, np.float64)
    t1s = np.zeros(schedule.n_steps, np.float64)
    for r, (w, st, sz) in enumerate(
            zip(schedule.workers, schedule.starts, schedule.sizes)):
        cost = csum[st + sz] - csum[st]
        t0s[r] = clocks[w]
        clocks[w] = np.float32(clocks[w] + cost)
        t1s[r] = clocks[w]
    return t0s, t1s
