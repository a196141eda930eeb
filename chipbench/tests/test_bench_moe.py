"""The DeepSeek-V3 routed-expert cell on the CPU, at a size a test holds.

A whole run of ``moe-dsv3-ep32-topics`` with d = 64, F = 32, a router
over 32 experts in 4 groups (top 4 of the best 2), 8 held, 512 tokens a
drain in blocks of 16, the kernels interpreted.  ``correct`` must come
out true for the program and false when its output, its tiles or its
routing are broken, and for the reference computed in float8.
"""
import numpy as np
import pytest

import repro.kernels
import repro.models.layers
from chipbench import cells, moe_control, moe_counts, run
from chipbench.drivers import moe_experts

NAME = "moe-dsv3-ep32-topics"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 5


def _tiny():
    cfg, tr = cells.load_cell(NAME)
    cfg = dict(cfg, hidden_size=64, moe_intermediate_size=32,
               num_experts_per_tok=4, n_group=4, topk_group=2,
               num_hidden_layers=2,
               published=dict(cfg["published"], n_routed_experts=32))
    tr = dict(tr, tokens=512, block=16, chunk=128, topics=8, pool=2)
    return cfg, tr


def _run(trace=False, device=CPU):
    bench = cells.load_benchmark()
    cfg, tr = _tiny()
    return run.run_cell(cells.workload(NAME, bench), cfg, tr, bench,
                        seed=SEED, seconds=0.2, trace=trace, device=device,
                        interpret=True)


def test_the_sound_run_is_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["checks"]) >= {"routing_mismatches",
                                "routing_tie_mismatches", "max_abs_err"}
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert r["checks"]["max_abs_err"]["value"] > 0  # bf16, not the reference


def _wrap_output(monkeypatch, fault):
    real = repro.kernels.moe_experts_persistent

    def broken(*args, **kw):
        y, sched = real(*args, **kw)
        return fault(y), sched

    monkeypatch.setattr(repro.kernels, "moe_experts_persistent", broken)


def test_an_altered_answer_is_not_correct(monkeypatch):
    _wrap_output(monkeypatch, lambda y: y.at[3, 5].add(1))
    r = _run()
    c = r["checks"]["max_abs_err"]
    assert not r["correct"] and c["value"] > c["limit"]


def test_half_of_the_tiles_left_out_is_not_correct(monkeypatch):
    """Each worker runs the first half of its claims only."""
    from repro.kernels.moe_experts import persistent as moe

    real = moe.persistent_call

    def half(nclaims, *args, **kw):
        return real(nclaims // 2, *args, **kw)

    monkeypatch.setattr(moe, "persistent_call", half)
    r = _run()
    assert not r["correct"]
    assert not r["checks"]["max_abs_err"]["value"] <= \
        r["checks"]["max_abs_err"]["limit"]


def test_a_changed_routing_is_not_correct(monkeypatch):
    """Every token's last choice is moved to the next expert."""
    real = repro.models.layers.moe_route

    def moved(x, router, bias, cfg):
        ids, w = real(x, router, bias, cfg)
        return ids.at[:, -1].set((ids[:, -1] + 1) % cfg.n_experts), w

    monkeypatch.setattr(repro.models.layers, "moe_route", moved)
    r = _run()
    assert not r["correct"]
    assert r["checks"]["routing_mismatches"]["value"] > 0


def test_the_control_fails_the_limits_the_program_keeps():
    """``moe_control.py``: the reference with its tokens, router and
    weights in float8_e4m3fn reads past the limits on every drain; the
    program's own readings stay inside them."""
    cfg, tr = _tiny()
    lim = cfg["limits"]
    got = list(moe_control.readings(NAME, SEED, True, interpret=True,
                                    cfg=cfg, traffic=tr))
    assert [g["drain"] for g in got] == [0, 1]
    for g in got:
        assert all(g["program"][n] <= lim[n] for n in lim)
        assert g["control"]["routing_mismatches"] > lim["routing_mismatches"]
        assert g["control"]["max_abs_err"] > lim["max_abs_err"]


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**40 + 3])
def test_every_seed_gives_the_same_held_loads(seed):
    cfg, tr = _tiny()
    want = moe_experts.Driver(cfg, tr, 1, interpret=True)
    want.setup()
    got = moe_experts.Driver(cfg, tr, seed, interpret=True)
    got.setup()
    assert len(got.loads) == 2
    assert all(np.array_equal(a, b) for a, b in zip(got.loads, want.loads))
    assert sum(int(n.sum()) for n in got.loads) > 0
    # the tokens are the same multiset in another order
    a, b = (np.asarray(d.x[0], np.float32) for d in (want, got))
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a, axis=0), np.sort(b, axis=0))


def test_topic_counts_follow_zipf():
    n = moe_experts.topic_counts(65536, 64, 1.0)
    assert n.sum() == 65536 and list(n) == sorted(n, reverse=True)
    assert n[0] / 65536 == pytest.approx(1 / sum(1 / k for k in
                                                 range(1, 65)), abs=1e-4)


def test_moe_counts_by_hand():
    # two experts with rows, one without; d = 3, F = 2, bf16
    loads = [2, 0, 1]
    assert moe_counts.expert_flops(loads, 3, 2) == 3 * (3 * 2 * 3 * 2)
    # weights: 2 experts x 3 matrices x 6 entries x 2 bytes; rows: 3 pairs
    # read and written, 3 wide
    assert moe_counts.expert_bytes(loads, 3, 2, itemsize=2) == \
        2 * 3 * 6 * 2 + 3 * 2 * 3 * 2


def test_the_recorded_loads_fit_the_stated_skew():
    tr = cells.load_cell(NAME)[1]
    L = np.asarray(tr["held_loads"])
    assert L.shape == (tr["pool"], 8)
    assert 2 <= L.max() / L.mean() <= 6
    assert round(L.max() / L.mean(), 3) == tr["held_load_max_over_mean"]
