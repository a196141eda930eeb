"""Host time per drain of ``repro.claim.launch`` and
``repro.compute.launch`` together, in ms: dispatching the protocol kernel
and the compute kernel."""


def read(ctx):
    prog = getattr(ctx, "program", None)
    return None if prog is None else prog.host_ms("repro.claim.launch",
                                                  "repro.compute.launch")
