"""Useful operations and bytes of a kernel call, from its shapes alone.

These count the work the algorithm needs, whatever implements it: no
padding, no query row past a sequence's length, no upcast.  So no kernel
can read above its roofline by doing more work than asked.
"""
from __future__ import annotations

from typing import Sequence


def causal_attention_flops(lengths: Sequence[int], heads: int,
                           head_dim: int) -> int:
    """Multiply-adds of causal attention, counted as 2 FLOPs each.

    Query ``i`` (0-based) of a row of length ``L`` attends ``i + 1`` keys:
    ``q.k`` and ``p.v`` take ``2 * D`` FLOPs per key each, so a row costs
    ``4 * H * D * L (L + 1) / 2``.
    """
    return sum(4 * heads * head_dim * (int(L) * (int(L) + 1) // 2)
               for L in lengths)


def attention_bytes(lengths: Sequence[int], heads: int, kv_heads: int,
                    head_dim: int, itemsize: int) -> int:
    """Bytes of q, k, v read and o written once, over live tokens only."""
    per_token = (2 * heads + 2 * kv_heads) * head_dim * itemsize
    return sum(int(L) * per_token for L in lengths)
