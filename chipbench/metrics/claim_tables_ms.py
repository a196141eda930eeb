"""Host time per drain of ``repro.tables``, in ms: the per-worker claim
tables (``DeviceSchedule.worker_lists``) built and uploaded."""


def read(ctx):
    prog = getattr(ctx, "program", None)
    return None if prog is None else prog.host_ms("repro.tables")
