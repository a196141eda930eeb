"""Drives the paper's Mandelbrot through the session path.

One drain is what a user of ``repro.dls`` writes for a self-scheduled
tile grid: ``dls.loop(N, technique, P, runtime="device")`` executed with
``executor="device"`` (the claim loop in the protocol kernel, the report
plane on the host), then ``mandelbrot_persistent`` on the schedule that
session made.  It ends when the counts image is on the device.

The per-tile cost model comes from one static-grid render in set-up.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.spans import span

#: role -> substring of the jitted module that runs it on the device
KERNELS = {"claim": "protocol_call", "mandel": "persistent_call"}
COMPUTE = "mandel"
#: tile of the one static-grid render that gives the cost model
STATIC_TILE = 128
#: the control's precision: the one below the configuration's float32
CONTROL_DTYPE = "bfloat16"


def rehearsal(cfg: dict, traffic: dict, claim_width):
    """(N, program, argument shapes) of the compute kernel the window
    drives, for ``rehearse.py`` to compile without the chip.

    ``claim_width(N, costs)`` gives the claim tables' width; the costs are
    uniform, since the chip's escape loop runs the full CT in every tile.
    """
    import functools

    import jax.numpy as jnp

    from repro.kernels.mandelbrot import persistent as mandel

    bh, bw, P = traffic["tile_h"], traffic["tile_w"], cfg["workers"]
    N = -(-cfg["height"] // bh) * -(-cfg["width"] // bw)
    C = claim_width(N, np.ones(N))
    prog = functools.partial(
        mandel.persistent_call, width=cfg["width"], height=cfg["height"],
        ct=cfg["ct"], xlim=tuple(cfg["xlim"]), ylim=tuple(cfg["ylim"]),
        block_h=bh, block_w=bw, interpret=False)
    i32 = jnp.int32
    return N, prog, [((P,), i32), ((P, C), i32), ((P, C), i32)]


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 interpret: bool = False):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.interpret = interpret
        self.W, self.H = cfg["width"], cfg["height"]
        self.P = cfg["workers"]
        self.bh, self.bw = traffic["tile_h"], traffic["tile_w"]
        self.technique = traffic["technique"]
        self.N = -(-self.H // self.bh) * -(-self.W // self.bw)
        self.pool = int(traffic.get("pool", 1))

    def _grid(self):
        c = self.cfg
        return dict(ct=c["ct"], xlim=tuple(c["xlim"]), ylim=tuple(c["ylim"]))

    def setup(self) -> None:
        import jax

        from repro.kernels import mandelbrot
        from repro.kernels.mandelbrot.persistent import mandelbrot_tile_costs

        static = jax.block_until_ready(mandelbrot(
            self.W, self.H, block_h=STATIC_TILE, block_w=STATIC_TILE,
            interpret=self.interpret, **self._grid()))
        self.costs = mandelbrot_tile_costs(np.asarray(static), self.bh,
                                           self.bw)
        for i in range(self.pool):
            self.drain(i)

    def drain(self, i: int):
        """(counts image, schedule record) of one loop, drained."""
        import jax

        from repro import dls
        from repro.kernels import mandelbrot_persistent

        with span("claim"):
            s = dls.loop(self.N, technique=self.technique, P=self.P,
                         runtime="device")
            s.execute(None, executor="device", costs=self.costs,
                      interpret=self.interpret)
        sched = s.runtime.schedule
        with span("compute"):
            out, _ = mandelbrot_persistent(
                self.W, self.H, block_h=self.bh, block_w=self.bw,
                workers=self.P, schedule=sched, interpret=self.interpret,
                **self._grid())
            jax.block_until_ready(out)
        lp_slot = s.runtime.counter_slots()[1]
        return out, (sched.starts, sched.sizes, sched.slab, lp_slot)

    def work(self, i: int):
        """No published peak for the vector unit: no roofline counts."""
        return None

    def reference(self, dtype=None):
        import jax.numpy as jnp

        return reference.escape_counts(
            width=self.W, height=self.H, dtype=dtype or jnp.float32,
            **self._grid())

    def control(self, p: int, dtype) -> int:
        """The reading of the reference in the program's place, computed
        in ``dtype``: its pixels off the float32 reference's."""
        return self.compare({0: (p, self.reference(dtype))})[0][
            "pixel_mismatches"]

    def compare(self, kept: dict) -> dict:
        """{drain: readings} of the kept drains' images.

        ``pixel_mismatches``: pixels whose escape count differs from the
        float32 reference's.
        """
        import jax.numpy as jnp

        ref = self.reference()
        return {i: {"pixel_mismatches": int(jnp.sum(out != ref))}
                for i, (_, out) in kept.items()}
