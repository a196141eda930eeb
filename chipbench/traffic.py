"""The one generator every traffic mix goes through.

A traffic file (``traffic/<name>.json``) holds parameters only.  Drains
arrive back to back from one client (a closed loop): the next loop is
handed over as soon as the previous one has drained.  The generator turns
the file and ``--seed`` into the pool of loops that the window cycles
through:

  pool           how many distinct loops the window cycles through
  batch          rows per loop (attention); absent for a tile grid
  lengths        row lengths: {"dist": "fixed", "value": L} or
                 {"dist": "lognormal", "median": m, "sigma": s,
                  "min": lo, "max": hi}
  check_sample   how many drains of the window are compared after it

Every seed gets the same batches, in another order: the lognormal is
sampled at the stratified quantiles ``(i + 0.5) / n`` of its
``n = pool * batch`` rows (the parameterisation of the lognormal prompt
lengths in ``repro/serve/workload.py``, with ``mu = ln(median)``), the
rows are dealt into batches the same way for every seed, and the seed
orders the batches and the rows within each.  So the work of every drain
in the pool is the same for every seed; a seed changes the order and the
values of q, k and v.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng(seed: int) -> np.random.Generator:
    """The host generator of a run; any non-negative integer seed."""
    return np.random.default_rng(int(seed))


def device_key_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.key``, drawn from ``seed``."""
    return int(rng(seed).integers(0, 2**31 - 1))


def lengths_multiset(spec: dict, n: int) -> np.ndarray:
    """The ``n`` row lengths every seed shares, ascending, int32."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int32)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    L = np.exp(mu + sigma * np.asarray(z))
    return np.clip(np.rint(L), spec["min"], spec["max"]).astype(np.int32)


def pool_lengths(traffic: dict, seed: int) -> np.ndarray:
    """(pool, batch) row lengths: the same batches for every seed.

    The ascending multiset is dealt round the pool, so batch ``j`` holds
    the ``j``-th length of every stratum of ``pool`` lengths.  The seed
    shuffles the order of the batches and of the rows within each.
    """
    pool, batch = int(traffic["pool"]), int(traffic["batch"])
    L = lengths_multiset(traffic["lengths"], pool * batch)
    batches = L.reshape(batch, pool).T.copy()   # row j: one per stratum
    r = rng(seed)
    batches = batches[r.permutation(pool)]
    for row in batches:
        r.shuffle(row)
    return batches


class Reservoir:
    """A uniform sample of ``k`` of the window's drains, drawn from the seed.

    Offered every drain in turn, it keeps what ``k`` of them produced
    (reservoir sampling), so the window holds at most ``k`` outputs
    however many drains it runs.
    """

    def __init__(self, k: int, seed: int):
        self.k = int(k)
        self._rng = rng(seed + 1)
        self.kept = {}   # drain index -> what that drain produced
        self.offered = 0

    def offer(self, item) -> None:
        i = self.offered
        self.offered += 1
        if len(self.kept) < self.k:
            self.kept[i] = item
            return
        j = int(self._rng.integers(0, i + 1))
        if j < self.k:
            del self.kept[sorted(self.kept)[j]]
            self.kept[i] = item
