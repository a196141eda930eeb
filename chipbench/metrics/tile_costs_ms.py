"""Host time per drain of ``repro.tile_costs``, in ms: the attention tile
cost model (``varlen_tile_costs``)."""


def read(ctx):
    prog = getattr(ctx, "program", None)
    return None if prog is None else prog.host_ms("repro.tile_costs")
