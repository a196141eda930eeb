"""Host time per drain of ``repro.claim``, in ms: all of ``claim_schedule``
(cost prefix sum and uploads, the protocol kernel's dispatch, the schedule
read back to the host)."""


def read(ctx):
    prog = getattr(ctx, "program", None)
    return None if prog is None else prog.host_ms("repro.claim")
