"""Useful attention FLOPs of the traced drains over the traced window
times the chip's bf16 peak, in %: the whole drain's share of the peak,
host gaps and the claim kernel included."""


def read(ctx):
    if not ctx.work or "attn" not in ctx.red.kernel_ns:
        return None
    seconds = ctx.red.window_ns * 1e-9
    return 100.0 * ctx.work["flops"] / (seconds * ctx.peaks["bf16_flops"])
