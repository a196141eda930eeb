"""Differential equivalence harness: fast path vs the event kernel.

The contract of ``repro.sim.fast`` (DESIGN.md Sec. 12) is *equivalence*,
not approximation: for every qualifying configuration,
``simulate_fast(cf)`` must return byte-for-byte the same ``SimResult``
the event kernel returns -- same canonical-JSON encoding, same floats,
same event ordering observable through latencies and grant counts.
Three layers enforce it:

  * the shared golden grid of ``_sim_golden_cases`` (rebuilt with
    ``collect_trace=False`` so the cases qualify), every qualifying
    case run through both engines and compared canonically;
  * a seeded random grid over technique x topology x P up to 1024 --
    heterogeneous continuous speeds (no structural boundary ties) and
    lognormal costs on both polling policies, which exercises the
    vectorized round, the tie walk, and the hazard-truncation path;
  * a hypothesis fuzz layer (when hypothesis is importable) over the
    same differential property plus the conservation-to-N and seed
    determinism invariants of ``test_invariants.py``.

Also covered: the ``_MTReplay`` Mersenne-Twister clone against CPython's
``random.Random`` (the Lock-Polling grant order must be bit-identical).
"""
import dataclasses
import json
import random

import numpy as np
import pytest

import _sim_golden_cases as gc
from repro.core.chunk_calculus import LoopSpec
from repro.core.sim import SimConfig, simulate
from repro.sim import (
    SweepCache,
    fast_qualifies,
    simulate_fast,
    simulate_fast_many,
    simulate_many,
)
from repro.sim.fast import _MTReplay

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised by the no-hypothesis job
    HAVE_HYPOTHESIS = False


def canon(r) -> str:
    return json.dumps(gc.encode_result(r), sort_keys=True)


def assert_same(cf, msg=""):
    """The differential property: fast == kernel, byte for byte."""
    rk = simulate(cf, engine="kernel")
    rf = simulate_fast(cf)
    assert canon(rk) == canon(rf), \
        f"fast path drifted from the event kernel: {msg}"


# ---------------------------------------------------------------------------
# golden grid, re-qualified (collect_trace off)
# ---------------------------------------------------------------------------

_CASES = gc.cases()
_KEYS = [c["key"] for c in _CASES]


def _no_trace(case: dict) -> SimConfig:
    return dataclasses.replace(gc.build_config(case), collect_trace=False)


@pytest.mark.parametrize("key", _KEYS)
def test_golden_grid_differential(key):
    case = next(c for c in _CASES if c["key"] == key)
    cf = _no_trace(case)
    assert fast_qualifies(cf)
    assert_same(cf, key)


def test_golden_grid_has_all_topologies():
    routed = {c["runtime"] for c in _CASES}
    assert routed == {"one_sided", "two_sided", "hierarchical"}


# ---------------------------------------------------------------------------
# seeded random grid (vector round, tie walk, hazard truncation)
# ---------------------------------------------------------------------------

_GRID = [
    (tech, impl, P)
    for tech in gc.NON_ADAPTIVE
    for impl in ("one_sided", "two_sided", "hierarchical")
    for P in (4, 64, 288, 1024)
]


def _random_config(tech, impl, P, seed, *, polling, continuous):
    rng = np.random.default_rng(seed)
    N = {4: 300, 64: 1500, 288: 4000, 1024: 8000}[P]
    sigma = np.sqrt(np.log(1.0 + 0.25))
    costs = rng.lognormal(np.log(2e-4) - sigma ** 2 / 2, sigma, size=N)
    if continuous:  # no structural boundary ties: the pure vector round
        speeds = rng.uniform(0.25, 1.0, size=P)
    else:  # golden-style speed tiles: exact ties + near-EPS hazards
        speeds = np.tile([1.0, 0.5, 0.25], P // 3 + 1)[:P]
    kw = {}
    if impl == "hierarchical":
        kw = dict(nodes=max(P // 32, 1), inner_technique="ss")
    return SimConfig(LoopSpec(tech, N=N, P=P), speeds, costs, impl=impl,
                     seed=seed, lock_polling_random=polling,
                     collect_trace=False, **kw)


@pytest.mark.parametrize("tech,impl,P", _GRID)
def test_random_grid_differential(tech, impl, P):
    # derive per-case determinism from the grid position
    seed = (hash((tech, impl)) & 0xFFFF) + P
    polling = (P % 2 == 0) if impl == "one_sided" else True
    cf = _random_config(tech, impl, P, seed,
                        polling=polling, continuous=(P % 3 != 0))
    assert_same(cf, f"{tech}/{impl}/P={P}")


@pytest.mark.parametrize("polling", [False, True])
@pytest.mark.parametrize("continuous", [False, True])
def test_contended_fifo_round(polling, continuous):
    """The regime the batch round targets: big FIFO backlog, window-
    bound workload -- both with structural ties (tiled speeds) and
    without (continuous speeds)."""
    cf = _random_config("ss", "one_sided", 288, 99,
                        polling=polling, continuous=continuous)
    cf = dataclasses.replace(cf, costs=np.full(cf.spec.N, 1e-5))
    assert_same(cf, f"contended polling={polling} continuous={continuous}")


def test_conservation_and_determinism():
    cf = _random_config("gss", "one_sided", 64, 5,
                        polling=True, continuous=True)
    r1 = simulate_fast(cf)
    r2 = simulate_fast(cf)
    assert canon(r1) == canon(r2)  # same seed -> same bytes
    assert int(np.sum(r1.per_pe_iters)) == cf.spec.N


# ---------------------------------------------------------------------------
# MT19937 replay: the Lock-Polling grant order must be bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 20240807, 999983])
def test_mt_replay_matches_random_random(seed):
    ref = random.Random(seed)
    rep = _MTReplay(seed)
    sizes = [1, 2, 3, 4, 5, 7, 8, 31, 32, 33, 100, 624, 625, 65537] * 60
    for n in sizes:
        assert rep.randrange(n) == ref.randrange(n)


def test_mt_replay_across_twist_boundary():
    # 624-word state: cross several refills with draws that reject often
    ref = random.Random(42)
    rep = _MTReplay(42)
    for _ in range(5000):
        assert rep.randrange(3) == ref.randrange(3)


# ---------------------------------------------------------------------------
# hypothesis layer
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(
        tech=st.sampled_from(gc.NON_ADAPTIVE),
        impl=st.sampled_from(["one_sided", "two_sided", "hierarchical"]),
        P=st.integers(min_value=1, max_value=40),
        N=st.integers(min_value=1, max_value=600),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
        polling=st.booleans(),
        tiled=st.booleans(),
    )
    def test_fuzz_differential(tech, impl, P, N, seed, polling, tiled):
        rng = np.random.default_rng(seed)
        costs = rng.lognormal(np.log(1e-4), 0.5, size=N)
        speeds = (np.tile([1.0, 0.5, 0.25], P // 3 + 1)[:P] if tiled
                  else rng.uniform(0.2, 1.0, size=P))
        kw = dict(nodes=max(P // 8, 1), inner_technique="ss") \
            if impl == "hierarchical" else {}
        cf = SimConfig(LoopSpec(tech, N=N, P=P), speeds, costs,
                       impl=impl, seed=seed, lock_polling_random=polling,
                       collect_trace=False, **kw)
        rk = simulate(cf, engine="kernel")
        rf = simulate_fast(cf)
        assert canon(rk) == canon(rf)
        assert int(np.sum(rf.per_pe_iters)) == N  # conservation to N


# ---------------------------------------------------------------------------
# batched sweeps: simulate_fast_many over one shared SweepCache
# ---------------------------------------------------------------------------


def test_batched_matches_per_config_on_golden_grid():
    """Sharing sweep setup must not change a single byte: the whole
    golden roster batched through one cache == per-config fast path."""
    cfs = [_no_trace(c) for c in _CASES]
    info = {}
    batched = simulate_fast_many(cfs, info=info)
    assert info["engines"] == ["fast-batch"] * len(cfs)
    for case, cf, r in zip(_CASES, cfs, batched):
        assert canon(r) == canon(simulate_fast(cf)), case["key"]


def _shared_roster(seed=0, P=64, N=1500):
    """A selection-style roster: every candidate references the *same*
    costs/speeds arrays (what replay.sweep builds from one calibration)."""
    rng = np.random.default_rng(seed)
    costs = rng.lognormal(np.log(2e-4), 0.5, size=N)
    speeds = rng.uniform(0.25, 1.0, size=P)
    out = []
    for tech in gc.NON_ADAPTIVE:
        for impl in ("one_sided", "two_sided", "hierarchical"):
            kw = dict(nodes=P // 16, inner_technique="ss") \
                if impl == "hierarchical" else {}
            out.append(SimConfig(LoopSpec(tech, N=N, P=P), speeds, costs,
                                 impl=impl, seed=seed, collect_trace=False,
                                 **kw))
    return out


def test_batched_shared_costs_random_roster():
    roster = _shared_roster(seed=7)
    cache = SweepCache()
    batched = simulate_fast_many(roster, cache=cache)
    # one shared cost array -> exactly one prefix-sum entry; the three
    # runtime variants of each technique share one chunk-table build
    assert len(cache._pref) == 1
    assert len(cache._speeds) == 1
    for cf, r in zip(roster, batched):
        assert canon(r) == canon(simulate_fast(cf))


def test_batched_mixed_roster_demotes_nonqualifying():
    """Adaptive / perturbed / traced candidates drop to the kernel
    mid-roster; their fast-qualifying peers stay batched."""
    roster = _shared_roster(seed=11)[:4]
    adaptive = dataclasses.replace(
        roster[0], spec=dataclasses.replace(roster[0].spec,
                                            technique="awf_b"))
    traced = dataclasses.replace(roster[1], collect_trace=True)
    mixed = [roster[0], adaptive, roster[2], traced, roster[3]]
    info = {}
    batched = simulate_fast_many(mixed, info=info)
    assert info["engines"] == ["fast-batch", "kernel", "fast-batch",
                               "kernel", "fast-batch"]
    for cf, r in zip(mixed, batched):
        assert canon(r) == canon(simulate(cf, engine="auto"))


def test_batched_hazard_demotion_mid_batch():
    """A tie/hazard-prone candidate (tiled speeds: exact boundary ties)
    mid-batch falls back to its serial cooldown without perturbing its
    batch peers."""
    roster = _shared_roster(seed=3)[:3]
    P, N = 64, 1500
    rng = np.random.default_rng(3)
    tiled = SimConfig(
        LoopSpec("ss", N=N, P=P),
        np.tile([1.0, 0.5, 0.25], P // 3 + 1)[:P],
        np.full(N, 1e-5),  # contended: backlogged window, max ties
        impl="one_sided", seed=3, collect_trace=False)
    batch = [roster[0], tiled, roster[1], roster[2]]
    for cf, r in zip(batch, simulate_fast_many(batch)):
        assert canon(r) == canon(simulate(cf, engine="kernel"))


def test_batched_budget_first_always_evaluated():
    roster = _shared_roster(seed=5)[:6]
    info = {}
    results = simulate_fast_many(roster, budget_s=0.0, info=info)
    assert results[0] is not None  # >= 1 candidate always evaluated
    assert results[1:] == [None] * 5
    assert info["engines"][0] == "fast-batch"
    assert info["engines"][1:] == [None] * 5
    # and the same contract through simulate_many's serial batched path
    info2 = {}
    results2 = simulate_many(roster, workers=1, budget_s=0.0, info=info2)
    assert results2[0] is not None and results2[1:] == [None] * 5
    assert canon(results2[0]) == canon(results[0])


def test_batched_engine_fast_raises_on_nonqualifying():
    roster = _shared_roster(seed=9)[:2]
    traced = dataclasses.replace(roster[1], collect_trace=True)
    with pytest.raises(ValueError, match="does not qualify"):
        simulate_fast_many([roster[0], traced], engine="fast")


def test_sweep_cache_pins_identity_and_evicts():
    cache = SweepCache(max_entries=2)
    a = np.ones(10)
    pref_a, list_a = cache.pref(a)
    assert cache.pref(a)[0] is pref_a  # hit: same object back
    b, c = np.ones(5), np.ones(7)
    cache.pref(b)
    cache.pref(c)  # third entry: evicts the oldest, cache stays bounded
    assert len(cache._pref) == 2
    # identity keying holds the keyed array: a stale id can't alias
    for ref, _, _ in cache._pref.values():
        assert ref is b or ref is c
