"""repro.device -- the paper's protocol *inside* the kernel.

Everything below the ``dls`` facade so far ran the claim loop on the
host: threads, real processes, or the DES, all fetch-adding counters a
host-side ``Window`` holds.  This package relocates the RMA window into
device memory and lets a fixed set of Pallas program instances claim
variable-sized tile chunks straight from it -- the ROADMAP's "DLS
on-device" item (see DESIGN.md Sec. 14):

  window.py         ``DeviceWindow``: the two protocol counters in an
                    int32 device-array slab behind the ordinary
                    ``Window`` contract (fallback ladder: on-device
                    atomics -> input/output-aliased slab update ->
                    interpret mode on CPU CI; plus an
                    ``io_callback`` shim for traced host-plane code).
  chunk_calculus.py jax-traceable SS/FSC/GSS/TSS/FAC2 closed forms,
                    index-for-index equal to ``core.chunk_calculus``.
  persistent.py     the protocol kernel: one persistent launch walks
                    Step 1-3 of the paper against the aliased slab and
                    emits the full (step, worker, start, size) schedule,
                    with the per-worker tables the compute kernels take
                    built on the device beside it.
  runtime.py        ``DeviceRuntime`` -- ``OneSidedRuntime`` over a
                    ``DeviceWindow`` (``dls.loop(runtime="device")``).
  executor.py       ``executor="device"``: run the in-kernel protocol,
                    adopt the final counters, replay the device-made
                    schedule into an ordinary ``SessionReport``.
"""
from .chunk_calculus import (  # noqa: F401
    DEVICE_TECHNIQUES,
    chunk_size_device,
    host_spec,
    plan_claims,
)
from .executor import execute_device  # noqa: F401
from .persistent import DeviceSchedule, claim_schedule, schedule_timeline  # noqa: F401
from .runtime import DEVICE_SPEC_TECHNIQUES, DeviceRuntime  # noqa: F401
from .window import DeviceWindow  # noqa: F401
