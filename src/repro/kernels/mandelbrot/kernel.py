"""Pallas TPU kernel for the paper's Mandelbrot variant (Algorithm 2).

The paper's second application iterates ``z <- z^4 + c`` per pixel until
``|z| >= 2`` or ``CT`` iterations -- a textbook *variable-cost* loop (interior
pixels burn the full CT, exterior pixels escape in a handful), i.e. exactly
the load-imbalance profile DLS techniques exist for.

TPU adaptation (vs. the paper's scalar CPU loop): escape-time iteration is a
*data-parallel masked loop* -- each VMEM tile of pixels runs the full-CT
``fori_loop`` on the VPU with an ``active`` mask; per-pixel early exit becomes
mask retirement.  Complex arithmetic is expressed over (re, im) float32 pairs
(TPUs have no complex dtype).  Tiles are (block_h x block_w) = (128, 128) by
default -- lane-aligned and small enough that 6 live f32 tiles fit easily in
VMEM (6 * 64 KiB).

The kernel needs **no input arrays**: pixel coordinates are derived from the
grid position via ``broadcasted_iota``, so the only HBM traffic is the final
count tile write -- the kernel is pure compute, which is what makes it a good
roofline probe for the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def escape_counts_tile(
    rows,
    cols,
    *,
    ct: int,
    width: int,
    height: int,
    xmin: float,
    xmax: float,
    ymin: float,
    ymax: float,
):
    """Escape counts for one tile of pixel indices (rows, cols) int32.

    Shared by the static-grid kernel below and the persistent
    self-scheduled variant (persistent.py) so the two paths can never
    drift numerically -- their outputs are compared exactly in tests.
    """
    dx = (xmax - xmin) / max(width - 1, 1)
    dy = (ymax - ymin) / max(height - 1, 1)
    cr = xmin + cols.astype(jnp.float32) * dx
    ci = ymin + rows.astype(jnp.float32) * dy

    def body(_, carry):
        zr, zi, cnt, active = carry
        # z^2
        zr2 = zr * zr - zi * zi
        zi2 = 2.0 * zr * zi
        # z^4 = (z^2)^2
        zr4 = zr2 * zr2 - zi2 * zi2
        zi4 = 2.0 * zr2 * zi2
        nzr = zr4 + cr
        nzi = zi4 + ci
        mag2 = nzr * nzr + nzi * nzi
        cnt = cnt + active
        live = active != 0
        still = jnp.where(mag2 < 4.0, active, 0)
        # freeze escaped pixels so overflow cannot propagate NaNs
        zr = jnp.where(live, nzr, zr)
        zi = jnp.where(live, nzi, zi)
        return zr, zi, cnt, still

    # The TPU compiler fixes each loop carry's tile layout from its initial
    # value.  A constant tile (or one coordinate alone) is laid out
    # replicated, and the body's full tiles cannot be relaid into that; nor
    # can a bool mask be carried.  So every carry starts from a tile built
    # out of both coordinates, and the live mask is carried as int32 0/1.
    zero = cr * 0.0 + ci * 0.0
    izero = zero.astype(jnp.int32)
    init = (zero, zero, izero, izero + 1)
    _, _, cnt, _ = jax.lax.fori_loop(0, ct, body, init)
    # out-of-image padding pixels carry zeros (sliced off by the wrapper)
    in_image = (rows < height) & (cols < width)
    return jnp.where(in_image, cnt, 0)


def _mandelbrot_kernel(
    counts_ref,
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
):
    bi = pl.program_id(0)
    bj = pl.program_id(1)
    rows = bi * block_h + jax.lax.broadcasted_iota(jnp.int32, (block_h, block_w), 0)
    cols = bj * block_w + jax.lax.broadcasted_iota(jnp.int32, (block_h, block_w), 1)
    counts_ref[...] = escape_counts_tile(
        rows, cols, ct=ct, width=width, height=height,
        xmin=xmin, xmax=xmax, ymin=ymin, ymax=ymax)


def mandelbrot_counts_pallas(
    width: int,
    height: int | None = None,
    *,
    ct: int = 1000,
    xlim=(-2.0, 1.0),
    ylim=(-1.5, 1.5),
    block_h: int = 128,
    block_w: int = 128,
    interpret: bool | None = None,
):
    """Escape-iteration counts, shape (height, width) int32."""
    from repro.kernels import resolve_interpret

    height = width if height is None else height
    interpret = resolve_interpret(interpret)
    gh = -(-height // block_h)
    gw = -(-width // block_w)
    kern = functools.partial(
        _mandelbrot_kernel,
        ct=ct,
        width=width,
        height=height,
        xmin=float(xlim[0]),
        xmax=float(xlim[1]),
        ymin=float(ylim[0]),
        ymax=float(ylim[1]),
        block_h=block_h,
        block_w=block_w,
    )
    out = pl.pallas_call(
        kern,
        grid=(gh, gw),
        out_specs=pl.BlockSpec((block_h, block_w), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gh * block_h, gw * block_w), jnp.int32),
        interpret=interpret,
    )()
    return out[:height, :width]
