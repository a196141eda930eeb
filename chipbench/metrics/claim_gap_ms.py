"""Device-idle time per drain from the end of the protocol kernel to the
start of the compute kernel, in ms: the host's part of the claim plane
(schedule copy-out, per-worker tables, the session's report plane)."""


def read(ctx):
    ns = ctx.red.claim_gap_ns
    return None if ns is None else ctx.red.per_drain(ns) / 1e6
