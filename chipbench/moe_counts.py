"""Useful operations and bytes of the routed expert layer, from its loads.

``loads`` holds the routed rows of each held expert in one drain: the
(token, expert) pairs the router sent to this chip's experts.  These count
the work the layer needs, whatever implements it: no padding row, no
weight fetched twice, so no kernel can read above its roofline by doing
more than asked.
"""
from __future__ import annotations

from typing import Sequence


def expert_flops(loads: Sequence[int], d: int, F: int) -> int:
    """Three (d x F) matmuls per routed row, a multiply-add as 2 FLOPs:
    ``6 * d * F`` per pair.  silu, the gating product and the routing
    weight are left out, as the MXU peak counts matmuls only."""
    return 6 * d * F * sum(int(n) for n in loads)


def expert_bytes(loads: Sequence[int], d: int, F: int,
                 itemsize: int) -> int:
    """Each expert with a routed row reads its three weight matrices once;
    each routed row is read and its output written once."""
    weights = sum(3 * d * F * itemsize for n in loads if int(n) > 0)
    return weights + 2 * d * itemsize * sum(int(n) for n in loads)
