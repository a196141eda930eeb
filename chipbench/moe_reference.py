"""Plain reference of DeepSeek-V3's routed experts on one chip's share.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the published description (the model's ``config.json`` and
the router of its inference code) and importing nothing of the program:

  * ``route``: sigmoid scores of ``x @ router``; the experts fall in
    ``n_group`` groups, each scored by the sum of its two best biased
    scores; the ``top_k`` best biased scores among the best ``topk_group``
    groups are chosen, and weighted by their unbiased scores, normalized
    and scaled.  Ranks come from sorts.  It also gives each token's
    margin from a tie: the smaller of the gap between its 4th and 5th
    group scores and the gap between its ``top_k``-th and next biased
    scores in the chosen groups;
  * ``held_part``: for each held expert, ``down(silu(x @ Wg) * (x @ Wu))``
    of the rows routed to it, weighted and summed per token.  Expert by
    expert, over the routed rows only, so that it fits at full size.

The tokens are taken in chunks so that no (T, n_experts) or (T, d)
float32 temporary of the whole batch is built but the output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
CHUNK = 8192


@functools.partial(jax.jit, static_argnames=("n_group", "topk_group",
                                             "top_k", "scaling"))
def _route_chunk(x, router, bias, *, n_group, topk_group, top_k, scaling):
    T, E = x.shape[0], router.shape[1]
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               router.astype(jnp.float32), precision=HI))
    choice = s + bias
    per = E // n_group
    gsum = jnp.sort(choice.reshape(T, n_group, per), -1)[..., -2:].sum(-1)
    gsorted = jnp.sort(gsum, -1)[:, ::-1]
    gkeep = gsum >= gsorted[:, topk_group - 1:topk_group]
    masked = jnp.where(jnp.repeat(gkeep, per, axis=1), choice, -jnp.inf)
    ranked = jnp.sort(masked, -1)[:, ::-1]
    chosen = masked >= ranked[:, top_k - 1:top_k]
    w = jnp.where(chosen, s, 0.0)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scaling
    margin = jnp.minimum(
        gsorted[:, topk_group - 1] - gsorted[:, topk_group],
        ranked[:, top_k - 1] - ranked[:, top_k])
    return w, margin


def route(x, router, bias, *, n_group, topk_group, top_k, scaling):
    """``(w (T, n_experts) f32, margin (T,) f32)``: the routing weights,
    zero off the chosen experts, and each token's margin from a tie."""
    outs = [_route_chunk(x[a:a + CHUNK], router, bias, n_group=n_group,
                         topk_group=topk_group, top_k=top_k,
                         scaling=scaling)
            for a in range(0, x.shape[0], CHUNK)]
    return (jnp.concatenate([o[0] for o in outs]),
            jnp.concatenate([o[1] for o in outs]))


@jax.jit
def _expert_rows(x_rows, wg, wu, wd, w_rows):
    x = x_rows.astype(jnp.float32)
    h = jax.nn.silu(jnp.dot(x, wg.astype(jnp.float32), precision=HI)) \
        * jnp.dot(x, wu.astype(jnp.float32), precision=HI)
    return jnp.dot(h, wd.astype(jnp.float32), precision=HI) * w_rows[:, None]


@jax.jit
def _add_rows(y, tok, rows):
    return y.at[tok].add(rows, mode="drop")


def held_part(x, w_gate, w_up, w_down, gates):
    """(T, d) float32: the held experts' weighted outputs, summed per
    token.  ``gates`` (T, E) holds the held experts' routing weights, in
    the order of the weights' first axis."""
    T, d = x.shape
    gates = np.asarray(gates)
    y = jnp.zeros((T, d), jnp.float32)
    for e in range(gates.shape[1]):
        tok = np.flatnonzero(gates[:, e])
        if not len(tok):
            continue
        n = -(-len(tok) // 4096) * 4096  # a few padded shapes, not one each
        pad = np.full(n, T, np.int64)
        pad[:len(tok)] = tok
        w = np.zeros(n, np.float32)
        w[:len(tok)] = gates[tok, e]
        rows = _expert_rows(jnp.take(x, pad, axis=0, mode="fill",
                                     fill_value=0),
                            w_gate[e], w_up[e], w_down[e], jnp.asarray(w))
        y = _add_rows(y, jnp.asarray(pad), rows)
    return y
