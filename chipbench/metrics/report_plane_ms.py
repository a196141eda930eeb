"""Host time per drain of ``repro.report``, in ms: the session's report
plane after the claim (``device/executor.py``: adopting the slab, the
modeled timeline, per-claim logging, the report)."""


def read(ctx):
    prog = getattr(ctx, "program", None)
    return None if prog is None else prog.host_ms("repro.report")
