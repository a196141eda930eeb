"""Device time of the routing modules per drain, in ms: the router over
all experts and the sort of the held pairs by expert."""


def read(ctx):
    ns = ctx.red.kernel_ns.get("route")
    return None if ns is None else ctx.red.per_drain(ns) / 1e6
