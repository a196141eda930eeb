"""Device time of the persistent Mandelbrot kernel per drain, in ms."""


def read(ctx):
    ns = ctx.red.kernel_ns.get("mandel")
    return None if ns is None else ctx.red.per_drain(ns) / 1e6
