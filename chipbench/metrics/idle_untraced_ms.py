"""Device-idle time per drain under no program span, in ms: the idle
inside ``bench.drain`` that the program's ``repro.*`` spans leave
unexplained."""


def read(ctx):
    prog = getattr(ctx, "program", None)
    if prog is None or not prog.host_ns:
        return None
    return prog.per_drain(prog.untraced_idle_ns) / 1e6
