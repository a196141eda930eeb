"""executor="device": drain the session's loop inside the protocol kernel.

One persistent-kernel launch (``device/persistent.py``) runs the whole
claim loop against the session's ``DeviceWindow`` slab; the executor then
adopts the mutated counters back into the window (so ``drained()`` /
``state()`` read the device truth), logs the granted claims into the
session's metrics plane in one call (``log_schedule``, as columns), and
emits an ordinary ``SessionReport`` whose ``chunk_times`` carry the
modeled earliest-free-worker timeline -- which is exactly what
``repro.replay`` capture -> calibrate -> gantt consume, unchanged.

``work_fn(start, stop)`` (optional) executes each chunk host-side in
grant order -- the hook tests use to assert coverage; the persistent
*compute* kernels (kernels/*/persistent.py) are the on-device way to
attach real work to the same schedule.
"""
from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from repro import tracing

from .persistent import claim_schedule, schedule_timeline
from .runtime import DeviceRuntime


def execute_device(session, work_fn: Optional[Callable[[int, int], None]] = None,
                   *, costs=None, interpret: Optional[bool] = None):
    """Drain ``session`` via the on-device claim loop; returns its report."""
    rt = session.runtime
    if not isinstance(rt, DeviceRuntime):
        raise ValueError(
            'executor="device" requires dls.loop(..., runtime="device") '
            f"(got a {type(rt).__name__} session)")
    spec = session.spec
    win = rt.window
    i_slot, lp_slot = rt.counter_slots()

    if costs is None:
        costs = np.ones(spec.N, np.float64)
    sched = claim_schedule(
        spec.technique, spec.N, spec.P,
        chunk=spec.min_chunk, max_chunk=spec.max_chunk,
        costs=costs, slab=win.slab(), i_slot=i_slot, lp_slot=lp_slot,
        interpret=interpret)
    with tracing.span("report") as span:
        win.adopt(sched.slab, n_rmw=sched.n_rmw)
        rt.schedule = sched

        t0s, t1s = schedule_timeline(sched, costs=costs)
        if work_fn is not None:
            for start, size in zip(sched.starts.tolist(), sched.sizes.tolist()):
                work_fn(start, start + size)
        session.log_schedule(sched.workers, sched.steps, sched.starts,
                             sched.sizes, t0s, t1s)
        if tracing.enabled():
            span.set_metadata(rows=sched.n_steps)
        return session.report("device", wall_time=sched.makespan())
