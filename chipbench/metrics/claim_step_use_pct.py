"""Granted claims over the protocol kernel's loop steps, in %: the
counters ``claims`` and ``steps`` of ``repro.claim``, summed over the
drains.  The rest of the steps find the loop drained and do nothing."""


def read(ctx):
    prog = getattr(ctx, "program", None)
    c = prog and prog.counters.get("repro.claim")
    if not c or not sum(c.get("steps", ())):
        return None
    return 100.0 * sum(c["claims"]) / sum(c["steps"])
