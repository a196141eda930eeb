"""Plain references of what the timed path computes, and their controls.

Straightforward ``jax.numpy``, written from the published definitions and
importing nothing of the program under test:

  * escape counts of ``z <- z^4 + c`` (arXiv:1901.02773's Mandelbrot):
    count an iteration while a pixel is live, retire it once
    ``|z|^2 >= 4``, at most ``ct`` iterations; in float32, or in a
    lower precision for the control;
  * causal attention with grouped key/value heads, softmax in float32 at
    ``highest`` matmul precision, one batch row at a time; the control
    takes its inputs rounded to a lower precision (``rounded``);
  * the chunks a self-scheduled loop grants (``chunk_plan``): the
    paper's closed forms, in exact integer arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def chunk_size(technique: str, i: int, N: int, P: int) -> int:
    """K'_i, the size of the chunk granted at scheduling step ``i``.

    The closed forms of arXiv:1901.02773 (its Eqs. 1-3 and Table 2): a
    function of the step index alone, which is what lets a worker compute
    its chunk after one atomic fetch-and-add on the step counter.  The
    smallest chunk is 1.

      static  ceil(N / P)
      ss      1
      gss     ceil(((P - 1) / P)^i * N / P)                        (Eq. 1)
      tss     K_0 - i * C, K_0 = ceil(N / 2P), K_last = 1,
              S = ceil(2N / (K_0 + K_last)), C = floor((K_0 - K_last) / (S - 1))
                                                                   (Eq. 2)
      fac2    ceil((1/2)^(floor(i / P) + 1) * N / P)               (Eq. 3)
    """
    if technique == "static":
        k = _ceil_div(N, P)
    elif technique == "ss":
        k = 1
    elif technique == "gss":
        k = _ceil_div((P - 1) ** i * N, P ** (i + 1))
    elif technique == "tss":
        k0 = _ceil_div(N, 2 * P)
        S = _ceil_div(2 * N, k0 + 1)
        C = 0 if S <= 1 else (k0 - 1) // (S - 1)
        k = k0 - i * C
    elif technique == "fac2":
        k = _ceil_div(N, P * 2 ** (i // P + 1))
    else:
        raise ValueError(f"no closed form for technique {technique!r}")
    return max(k, 1)


def chunk_plan(technique: str, N: int, P: int):
    """(sizes, starts) of the chunks that drain ``[0, N)`` in step order.

    Each step takes ``chunk_size`` iterations, the last one only what is
    left, so the chunks partition ``[0, N)``.
    """
    sizes, start = [], 0
    while start < N:
        sizes.append(min(chunk_size(technique, len(sizes), N, P), N - start))
        start += sizes[-1]
    sizes = np.asarray(sizes, np.int64)
    return sizes, np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)


@functools.partial(jax.jit, static_argnames=(
    "width", "height", "ct", "xlim", "ylim", "dtype"))
def escape_counts(*, width: int, height: int, ct: int, xlim, ylim,
                  dtype=jnp.float32):
    """(height, width) int32 escape counts over the viewport."""
    dx = (xlim[1] - xlim[0]) / max(width - 1, 1)
    dy = (ylim[1] - ylim[0]) / max(height - 1, 1)
    cols = jnp.arange(width, dtype=dtype)[None, :]
    rows = jnp.arange(height, dtype=dtype)[:, None]
    cr = jnp.broadcast_to(xlim[0] + cols * jnp.asarray(dx, dtype),
                          (height, width)).astype(dtype)
    ci = jnp.broadcast_to(ylim[0] + rows * jnp.asarray(dy, dtype),
                          (height, width)).astype(dtype)

    def body(_, carry):
        zr, zi, count, live = carry
        zr2, zi2 = zr * zr - zi * zi, 2 * zr * zi
        nzr = zr2 * zr2 - zi2 * zi2 + cr
        nzi = 2 * zr2 * zi2 + ci
        count = count + live.astype(jnp.int32)
        zr = jnp.where(live, nzr, zr)
        zi = jnp.where(live, nzi, zi)
        return zr, zi, count, live & (nzr * nzr + nzi * nzi < 4)

    zero = jnp.zeros((height, width), dtype)
    init = (zero, zero, jnp.zeros((height, width), jnp.int32),
            jnp.ones((height, width), bool))
    return jax.lax.fori_loop(0, ct, body, init)[2]


@jax.jit
def causal_attention_row(q, k, v):
    """Causal attention of one batch row: q (H, T, D), k/v (Hkv, T, D).

    float32 at ``highest`` matmul precision, whatever the inputs' type.
    Query ``i`` attends keys ``0..i``, so rows below a sequence's length
    never see a padded key.
    """
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    H, T, D = q.shape
    group = H // k.shape[0]
    k = jnp.repeat(k, group, axis=0)
    v = jnp.repeat(v, group, axis=0)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision=hi) * (D ** -0.5)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v, precision=hi)


def rounded(x, dtype):
    """``x`` stored in ``dtype``: a cast of its own, outside any jit.

    Inside one compiled program XLA may drop a round trip through a
    narrower type (excess precision is allowed by default); an array
    materialised in ``dtype`` cannot skip the rounding.
    """
    return jax.block_until_ready(jnp.asarray(x).astype(dtype))
