"""Persistent self-scheduled Mandelbrot: a fixed worker grid, device claims.

The static entry point (``ops.mandelbrot``) launches one program per tile.
This variant launches ``workers`` persistent program instances and lets the
device-window protocol (``repro.device``, DESIGN.md Sec. 14) decide which
tiles each one executes: the claim loop runs on-device in the protocol
kernel, producing per-worker claim tables (variable-sized chunks of the
linearized tile space); each persistent program then walks its own table
with dynamic-slice writes into the shared counts image.

Pixel math is ``escape_counts_tile`` -- the *same* function the static
kernel calls -- so the two paths are exactly equal (pinned in tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing
from repro.device.persistent import DeviceSchedule, claim_schedule

from .kernel import escape_counts_tile


def _persistent_kernel(
    nclaims_ref,  # (W,)   int32 SMEM -- claims per worker
    starts_ref,   # (W*C,) int32 SMEM -- first tile of each claim
    sizes_ref,    # (W*C,) int32 SMEM -- tiles in each claim
    out_ref,      # (gh*block_h, gw*block_w) int32 -- whole counts image
    *,
    ct: int,
    width: int,
    height: int,
    xmin: float,
    xmax: float,
    ymin: float,
    ymax: float,
    block_h: int,
    block_w: int,
    gw: int,
    C: int,
):
    w = pl.program_id(0)

    def claim_body(c, _):
        st = starts_ref[w * C + c]
        sz = sizes_ref[w * C + c]

        def tile_body(t, __):
            tile = st + t
            ti = tile // gw
            tj = tile - ti * gw
            r0 = pl.multiple_of(ti * block_h, block_h)
            c0 = pl.multiple_of(tj * block_w, block_w)
            rows = r0 + jax.lax.broadcasted_iota(
                jnp.int32, (block_h, block_w), 0)
            cols = c0 + jax.lax.broadcasted_iota(
                jnp.int32, (block_h, block_w), 1)
            cnt = escape_counts_tile(
                rows, cols, ct=ct, width=width, height=height,
                xmin=xmin, xmax=xmax, ymin=ymin, ymax=ymax)
            out_ref[pl.ds(r0, block_h), pl.ds(c0, block_w)] = cnt
            return __

        jax.lax.fori_loop(0, sz, tile_body, 0)
        return _

    jax.lax.fori_loop(0, nclaims_ref[w], claim_body, 0)


@functools.partial(jax.jit, static_argnames=(
    "width", "height", "ct", "xlim", "ylim", "block_h", "block_w",
    "interpret"))
def persistent_call(nclaims, starts, sizes, *, width: int, height: int,
                    ct: int, xlim, ylim, block_h: int, block_w: int,
                    interpret: bool):
    """The persistent kernel's ``pallas_call``: jittable, arrays in and out.

    ``nclaims (W,)``, ``starts``/``sizes (W, C)`` int32 are the per-worker
    claim tables (``DeviceSchedule.tables``); they ride in SMEM as
    scalar-prefetch operands, because each program reads them one entry
    at a time.  Returns the padded ``(gh*block_h, gw*block_w)`` image.
    """
    workers, C = starts.shape
    gh = -(-height // block_h)
    gw = -(-width // block_w)
    kern = functools.partial(
        _persistent_kernel,
        ct=ct, width=width, height=height,
        xmin=float(xlim[0]), xmax=float(xlim[1]),
        ymin=float(ylim[0]), ymax=float(ylim[1]),
        block_h=block_h, block_w=block_w, gw=gw, C=C,
    )
    shape = (gh * block_h, gw * block_w)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(workers,),
            in_specs=[],
            # every program maps to the same (whole-image) block: the
            # claims partition [0, N), so together the workers write
            # every tile once
            out_specs=pl.BlockSpec(shape, lambda w, *_: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
        interpret=interpret,
        name="mandelbrot_persistent",
    )(nclaims, starts.reshape(-1), sizes.reshape(-1))


def mandelbrot_persistent(
    width: int,
    height: int | None = None,
    *,
    ct: int = 1000,
    xlim=(-2.0, 1.0),
    ylim=(-1.5, 1.5),
    block_h: int = 128,
    block_w: int = 128,
    technique: str = "gss",
    workers: int = 4,
    chunk: int = 1,
    interpret: bool | None = None,
    costs=None,
    schedule: DeviceSchedule | None = None,
):
    """Self-scheduled counts image; returns ``(counts, DeviceSchedule)``.

    The loop is the linearized tile grid (N = ceil(h/bh) * ceil(w/bw));
    ``technique``/``workers``/``chunk`` parameterize the device claim loop.
    Pass ``schedule`` to reuse a previously-claimed schedule (it must match
    this grid), or ``costs`` (length N, per-tile) to shape the assignment.
    """
    from repro.kernels import resolve_interpret

    height = width if height is None else height
    interpret = resolve_interpret(interpret)
    gh = -(-height // block_h)
    gw = -(-width // block_w)
    N = gh * gw

    if schedule is None:
        schedule = claim_schedule(
            technique, N, workers, chunk=chunk, costs=costs,
            interpret=interpret)
    if schedule.N != N or schedule.P != workers:
        raise ValueError(
            f"schedule is for (N={schedule.N}, P={schedule.P}), "
            f"this grid needs (N={N}, P={workers})")
    tables = schedule.launch_tables()
    with tracing.launch("compute.launch", persistent_call):
        out = persistent_call(
            *tables, width=width, height=height, ct=ct, xlim=tuple(xlim),
            ylim=tuple(ylim), block_h=block_h, block_w=block_w,
            interpret=interpret)
    return out[:height, :width], schedule


def mandelbrot_tile_costs(counts, block_h: int = 128, block_w: int = 128):
    """Per-tile cost model from a counts image: total escape iterations.

    Linearized row-major over the tile grid -- feed to ``claim_schedule`` /
    ``mandelbrot_persistent(costs=...)`` so the claim loop sees the real
    variable-cost profile (interior tiles burn CT per pixel, exterior ones
    almost nothing).
    """
    counts = np.asarray(counts)
    h, w = counts.shape
    gh = -(-h // block_h)
    gw = -(-w // block_w)
    padded = np.zeros((gh * block_h, gw * block_w), np.float64)
    padded[:h, :w] = counts
    return (padded.reshape(gh, block_h, gw, block_w)
                  .sum(axis=(1, 3)).reshape(gh * gw))
