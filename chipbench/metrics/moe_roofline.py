"""Share of its roofline that the expert kernel's module reaches, in %.

The least time the chip could take for the traced drains' useful work
(``moe_counts.py``: ``6 d F`` FLOPs per routed pair; each used expert's
weights once and each routed row read and written once, in bf16) is the
larger of FLOPs over the bf16 peak and bytes over the HBM bandwidth; the
share is that time over the module's device time.
"""


def read(ctx):
    ns = ctx.red.kernel_ns.get("moe")
    if ns is None or not ctx.work:
        return None
    w, pk = ctx.work, ctx.peaks
    least_s = max(w["flops"] / pk["bf16_flops"],
                  w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns * 1e-9)
