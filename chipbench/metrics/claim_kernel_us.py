"""Device time of the protocol kernel (``protocol_call``) per drain, in us."""


def read(ctx):
    ns = ctx.red.kernel_ns.get("claim")
    return None if ns is None else ctx.red.per_drain(ns) / 1e3
