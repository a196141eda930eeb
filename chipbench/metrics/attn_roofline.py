"""Share of its roofline that the persistent attention kernel reaches, in %.

The least time the chip could take for the traced drains' useful work
(``counts.py``: FLOPs of causal attention and bytes of q, k, v and o over
live tokens) is the larger of FLOPs over the bf16 peak and bytes over the
HBM bandwidth; the share is that time over the kernel's device time.
"""


def read(ctx):
    ns = ctx.red.kernel_ns.get("attn")
    if ns is None or not ctx.work:
        return None
    w, pk = ctx.work, ctx.peaks
    least_s = max(w["flops"] / pk["bf16_flops"],
                  w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns * 1e-9)
