"""Pallas TPU kernels (interpret mode on CPU, compiled on the TPU).

  mandelbrot      -- paper app 2: escape-time z<-z^4+c (variable-cost loop)
  spin_image      -- paper app 1: PSIA histogram via one-hot reduction
  flash_attention -- fused attention (causal/SWA/GQA), transformer hot spot
  ssd_scan        -- Mamba2 SSD chunked scan with VMEM-carried state

``mandelbrot`` and ``flash_attention`` additionally ship *persistent
self-scheduled* variants (``*_persistent``): a fixed worker grid claiming
variable-sized tile chunks through the device-window protocol of
``repro.device`` instead of a static grid -- DESIGN.md Sec. 14.

  moe_experts     -- persistent only: one chip's routed experts of a MoE
                     layer (DeepSeek-V3), (expert, row-block) tiles whose
                     costs come from the router, weights streamed from HBM
"""
import jax

#: Kernel calls this process resolved to interpret mode.  A run on the
#: chip asserts it stays 0: no kernel there may quietly interpret.
interpreted_calls = 0


def resolve_interpret(interpret=None) -> bool:
    """The one interpret-mode autodetect every kernel entry point shares.

    ``None`` means "interpret exactly when there is no accelerator"
    (Pallas kernels run under the interpreter on the CPU backend, compiled
    otherwise); an explicit bool passes through.  Defined before the
    submodule re-exports below so kernel modules can import it from this
    package without a cycle.
    """
    global interpreted_calls
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    interpreted_calls += bool(interpret)
    return bool(interpret)


from .flash_attention.ops import attention_oracle, flash_attention  # noqa: F401,E402
from .flash_attention.persistent import flash_attention_persistent  # noqa: F401,E402
from .mandelbrot.ops import mandelbrot, mandelbrot_ref  # noqa: F401,E402
from .mandelbrot.persistent import mandelbrot_persistent  # noqa: F401,E402
from .moe_experts.persistent import moe_experts_persistent  # noqa: F401,E402
from .spin_image.ops import spin_images, spin_images_oracle  # noqa: F401,E402
from .ssd_scan.ops import ssd_scan, ssd_scan_oracle  # noqa: F401,E402
