"""Device time of the expert kernel's module per drain, in ms: the rows'
gather, the streaming expert kernel and the combine."""


def read(ctx):
    ns = ctx.red.kernel_ns.get("moe")
    return None if ns is None else ctx.red.per_drain(ns) / 1e6
