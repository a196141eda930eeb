"""Host time per drain of ``repro.claim.readback``, in ms: the schedule's
arrays copied to the host, which waits for the protocol kernel."""


def read(ctx):
    prog = getattr(ctx, "program", None)
    return None if prog is None else prog.host_ms("repro.claim.readback")
