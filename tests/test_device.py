"""repro.device: the RMA window relocated to device memory (DESIGN.md 14).

Everything runs under the Pallas interpreter on CPU -- the same protocol
kernel an accelerator compiles.  The load-bearing pins: the on-device
chunk calculus and claim loop match the host closed forms *index for
index* (golden parity), claims partition [0, N) exactly (conservation),
and a device-made session report round-trips through the ordinary
replay plane (capture -> calibrate -> simulate -> gantt) unchanged.
"""
import functools

import numpy as np
import pytest

from repro import dls
from repro.core.chunk_calculus import chunk_sizes_closed, plan
from repro.core.rma import HierarchicalWindow, make_window
from repro.core.scheduler import Claim
from repro.device import (
    DEVICE_SPEC_TECHNIQUES,
    DEVICE_TECHNIQUES,
    DeviceRuntime,
    DeviceWindow,
    chunk_size_device,
    claim_schedule,
    host_spec,
    schedule_timeline,
)

pytestmark = pytest.mark.skipif(
    not DeviceWindow.available(),
    reason=f"DeviceWindow unavailable: {DeviceWindow.availability()[1]}")

# The seeded grid the golden parity pins.  (513, 3) is the canonical GSS
# f32-vs-f64 ceil-boundary case; the larger combos ride the slow tier
# (the `device` CI job runs them explicitly, tier-1 stays in budget).
PARITY_GRID = (
    (100, 4),
    (513, 3),
    pytest.param(1000, 7, marks=pytest.mark.slow),
    pytest.param(4096, 8, marks=pytest.mark.slow),
)


# ---------------------------------------------------------------------------
# golden parity: on-device closed forms vs core.chunk_calculus
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("technique", DEVICE_TECHNIQUES)
@pytest.mark.parametrize("N,P", PARITY_GRID)
def test_chunk_size_device_matches_host(technique, N, P):
    import jax.numpy as jnp

    chunk = 3 if technique in ("ss", "fsc", "tss") else 1
    spec = host_spec(technique, N, P, chunk=chunk)
    from repro.core.chunk_calculus import max_steps_bound
    S = max_steps_bound(spec)
    idx = np.arange(S, dtype=np.int64)
    want = chunk_sizes_closed(spec, idx, np).astype(np.int64)
    got = np.asarray(
        chunk_size_device(technique, jnp.arange(S, dtype=jnp.int32),
                          N=N, P=P, chunk=chunk), np.int64)
    assert np.array_equal(got, want), (
        f"{technique} N={N} P={P}: first mismatch at "
        f"i={int(np.argmax(got != want))}")


@pytest.mark.parametrize("technique", DEVICE_SPEC_TECHNIQUES)
@pytest.mark.parametrize("N,P", PARITY_GRID)
def test_claim_schedule_matches_host_plan(technique, N, P):
    sched = claim_schedule(technique, N, P)
    sizes, starts = plan(host_spec(technique, N, P))
    assert sched.n_steps == len(sizes)
    assert np.array_equal(sched.sizes, sizes)
    assert np.array_equal(sched.starts, starts)
    assert np.array_equal(sched.steps, np.arange(sched.n_steps))
    # conservation: the device-made claims partition [0, N) exactly
    assert int(sched.sizes.sum()) == N
    cov = np.zeros(N, np.int64)
    for st, sz in zip(sched.starts, sched.sizes):
        cov[st:st + sz] += 1
    assert (cov == 1).all()
    # every worker's claim count is accounted
    assert int(sched.counts.sum()) == sched.n_steps
    assert sched.n_rmw == 2 * sched.n_steps


def test_claim_schedule_max_chunk_and_min_chunk():
    sched = claim_schedule("gss", 200, 4, chunk=2, max_chunk=30)
    sizes, starts = plan(host_spec("gss", 200, 4, chunk=2, max_chunk=30))
    assert np.array_equal(sched.sizes, sizes)
    assert sched.sizes.max() <= 30
    assert int(sched.sizes.sum()) == 200


def test_claim_schedule_resumes_from_nonzero_counters():
    """Nonzero window counters resume a partially-drained loop."""
    import jax.numpy as jnp

    full = claim_schedule("fac2", 150, 3)
    k = 4  # pretend the first k claims already happened
    slab = jnp.zeros(2, jnp.int32)
    slab = slab.at[0].set(k)
    slab = slab.at[1].set(int(full.starts[k]))
    rest = claim_schedule("fac2", 150, 3, slab=slab)
    assert np.array_equal(rest.sizes, full.sizes[k:])
    assert np.array_equal(rest.starts, full.starts[k:])
    assert np.array_equal(rest.steps, full.steps[k:])


@pytest.mark.parametrize("resumed", [False, True], ids=["fresh", "resumed"])
@pytest.mark.parametrize("technique", ["gss", "fac2", "tss", "ss"])
def test_claim_schedule_reads_back_what_the_kernel_wrote(technique, resumed):
    """One packed vector comes back, and every schedule field sliced from
    it equals the kernel's own output read back array by array."""
    import jax
    import jax.numpy as jnp

    from repro.core.chunk_calculus import max_steps_bound
    from repro.device import persistent

    N, P = 150, 3
    costs = np.linspace(1.0, 3.0, N) ** 2
    slab = jnp.zeros(2, jnp.int32)
    if resumed:  # the first 4 claims already happened
        full = claim_schedule(technique, N, P)
        slab = slab.at[0].set(4).at[1].set(int(full.starts[4]))
    S = int(max_steps_bound(host_spec(technique, N, P)))
    csum = np.zeros(N + 1, np.float32)
    np.cumsum(costs, out=csum[1:])
    kw = dict(technique=technique, N=N, P=P, chunk=1, max_chunk=None, S=S,
              i_slot=0, lp_slot=1, interpret=True)

    out = persistent.protocol_call(slab, jnp.asarray(csum), **kw)
    assert len(out) == 3  # the slab, the packed schedule, the tables
    assert out[1].dtype == jnp.int32 and out[1].shape == (4 * S + 2 * P,)

    _, steps, workers, starts, sizes, clocks, counts = (
        np.asarray(a) for a in jax.jit(functools.partial(
            persistent._protocol_outputs, **kw))(slab, jnp.asarray(csum)))
    n = int((workers >= 0).sum())
    want = dict(steps=steps[:n], workers=workers[:n], starts=starts[:n],
                sizes=sizes[:n], counts=counts.astype(np.int64))
    sched = claim_schedule(technique, N, P, costs=costs, slab=slab)
    assert sched.n_steps == n > 0
    for name, value in want.items():
        got = getattr(sched, name)
        assert got.dtype == value.dtype and np.array_equal(got, value), name
    assert sched.clocks.dtype == np.float32
    assert sched.clocks.tobytes() == clocks.tobytes()  # bit for bit
    assert len(np.unique(sched.clocks)) > 1  # the costs are not uniform
    assert int(np.asarray(sched.slab)[1]) >= N


def _host_walk(technique, N, P, chunk, costs, k):
    """The claims of the closed-form plan from its ``k``-th on, each to the
    earliest-free worker (float32 clocks, ties to the lowest index), as
    per-worker lists of ``(start, size)`` in protocol order."""
    sizes, starts = plan(host_spec(technique, N, P, chunk=chunk))
    csum = np.zeros(N + 1, np.float32)
    np.cumsum(np.asarray(costs, np.float64), out=csum[1:])
    clocks = np.zeros(P, np.float32)
    lists = [[] for _ in range(P)]
    for st, sz in zip(starts[k:], sizes[k:]):
        w = int(np.argmin(clocks))
        clocks[w] = np.float32(clocks[w] + (csum[st + sz] - csum[st]))
        lists[w].append((int(st), int(sz)))
    return lists, starts


@pytest.mark.parametrize("resumed", [False, True], ids=["fresh", "resumed"])
@pytest.mark.parametrize("technique", DEVICE_TECHNIQUES)
def test_device_tables_are_the_host_walk(technique, resumed):
    """The per-worker tables ``protocol_call`` builds on the device hold
    each worker's claims in protocol order, then zero-sized padding, and
    are as wide as the plan: every worker fits, even one that takes all
    claims of a run of zero-cost tiles."""
    import jax.numpy as jnp

    from repro.device import plan_claims

    N, P, chunk = 150, 3, 3
    costs = np.linspace(1.0, 3.0, N) ** 2
    costs[40:90] = 0.0
    k, slab = 0, None
    if resumed:  # the first 4 claims already happened
        _, starts = _host_walk(technique, N, P, chunk, costs, 0)
        k = min(4, len(starts) - 1)
        slab = jnp.array([k, starts[k]], jnp.int32)
    lists, _ = _host_walk(technique, N, P, chunk, costs, k)
    sched = claim_schedule(technique, N, P, chunk=chunk, costs=costs,
                           slab=slab)
    nclaims, starts, sizes = (np.asarray(t) for t in sched.tables)
    C = plan_claims(technique, N, P, chunk)
    assert starts.shape == sizes.shape == (P, C)
    assert C >= max(map(len, lists))
    assert nclaims.tolist() == [len(rows) for rows in lists]
    for w, rows in enumerate(lists):
        n = len(rows)
        assert list(zip(starts[w, :n].tolist(), sizes[w, :n].tolist())) \
            == rows
        assert not sizes[w, n:].any() and not starts[w, n:].any()
    assert nclaims.tolist() == sched.counts.tolist()


@pytest.mark.parametrize("technique", DEVICE_TECHNIQUES)
def test_a_resumed_loop_takes_no_more_claims_than_the_plan(technique):
    """From every state the protocol leaves the counters in (the plan's
    first ``k`` claims granted), the rest of the loop fits the tables'
    width: zero costs send every claim to one worker."""
    import jax.numpy as jnp

    from repro.device import plan_claims

    N, P = 97, 4
    C = plan_claims(technique, N, P, 1)
    _, starts = _host_walk(technique, N, P, 1, np.ones(N), 0)
    assert C == len(starts)
    for k in range(C + 1):
        lp = int(starts[k]) if k < C else N
        sched = claim_schedule(technique, N, P, costs=np.zeros(N),
                               slab=jnp.array([k, lp], jnp.int32))
        nclaims = np.asarray(sched.tables[0])
        assert sched.n_steps == C - k == int(nclaims.sum())
        assert nclaims.tolist() == [C - k] + [0] * (P - 1)


def test_counters_off_the_plan_fail_on_read_not_in_silence():
    """Counters set by hand past the plan's step index grant more claims
    than the tables hold; the host view says so instead of a schedule
    whose compute dropped claims."""
    import jax.numpy as jnp

    from repro.device import plan_claims

    sched = claim_schedule("gss", 100, 4,
                           slab=jnp.array([40, 0], jnp.int32))
    assert sched.tables[1].shape == (4, plan_claims("gss", 100, 4))
    with pytest.raises(RuntimeError, match="not left by"):
        sched.starts


def test_schedule_timeline_consistency():
    costs = np.linspace(1.0, 3.0, 400)
    sched = claim_schedule("tss", 400, 5, costs=costs)
    t0s, t1s = schedule_timeline(sched, costs=costs)
    assert np.isclose(max(t1s), sched.makespan(), rtol=1e-6)
    # per-worker intervals are back-to-back and non-overlapping
    for w in range(5):
        rows = [r for r in range(sched.n_steps) if sched.workers[r] == w]
        for a, b in zip(rows, rows[1:]):
            assert t1s[a] <= t0s[b] + 1e-9


def _timeline_by_loop(sched, costs):
    """The kernel's clock walk replayed one claim at a time: the oracle of
    the vectorised ``schedule_timeline``."""
    csum = np.zeros(sched.N + 1, np.float32)
    np.cumsum(np.asarray(costs, np.float64), out=csum[1:])
    clocks = np.zeros(sched.P, np.float32)
    t0s = np.zeros(sched.n_steps, np.float64)
    t1s = np.zeros(sched.n_steps, np.float64)
    for r, (w, st, sz) in enumerate(
            zip(sched.workers, sched.starts, sched.sizes)):
        cost = csum[st + sz] - csum[st]
        t0s[r] = clocks[w]
        clocks[w] = np.float32(clocks[w] + cost)
        t1s[r] = clocks[w]
    return t0s, t1s


def _report_by_claim_loop(session, sched, costs, work_fn=None):
    """The device executor's report plane as one call per claim: a
    ``log_claim`` in grant order, then a ``record_remote`` in completion
    order (the oracle of ``log_schedule``)."""
    session.runtime.window.adopt(sched.slab, n_rmw=sched.n_rmw)
    t0s, t1s = _timeline_by_loop(sched, costs)
    rows = []
    for r in range(sched.n_steps):
        w = int(sched.workers[r])
        c = Claim(step=int(sched.steps[r]), start=int(sched.starts[r]),
                  size=int(sched.sizes[r]))
        session.log_claim(w, c)
        if work_fn is not None:
            work_fn(c.start, c.stop)
        rows.append((w, c, float(t0s[r]), float(t1s[r])))
    for w, c, t0, t1 in sorted(rows, key=lambda x: (x[2], x[3], x[0])):
        session.record_remote(w, c.size, t1 - t0, sched_seconds=0.0,
                              claim=c, t_start=t0, t_end=t1)
    return session.report("device", wall_time=sched.makespan())


# uniform costs tie every worker's clock; the others have zero-cost
# iterations, equal-cost runs and rising costs
TIMELINE_COSTS = {
    "uniform": lambda N: np.ones(N),
    "zeros-and-ties": lambda N: np.where(np.arange(N) % 5 < 2, 0.0,
                                         1.0 + (np.arange(N) // 40)),
    "varying": lambda N: np.linspace(1.0, 3.0, N) ** 2,
}


@pytest.mark.parametrize("costs", sorted(TIMELINE_COSTS))
@pytest.mark.parametrize("technique", ["ss", "gss", "fac2", "tss"])
def test_schedule_timeline_is_the_clock_walk_bit_for_bit(technique, costs):
    N, P = 200, 5
    c = TIMELINE_COSTS[costs](N)
    sched = claim_schedule(technique, N, P, costs=c)
    t0s, t1s = schedule_timeline(sched, costs=c)
    want0, want1 = _timeline_by_loop(sched, c)
    assert t0s.tobytes() == want0.tobytes()
    assert t1s.tobytes() == want1.tobytes()
    # and the kernel's own clocks: each worker's last t1, in float32
    last = np.zeros(P, np.float32)
    for w, t1 in zip(sched.workers, t1s):
        last[w] = t1
    assert last.tobytes() == sched.clocks.tobytes()
    assert float(t1s.max()) == sched.makespan()


@pytest.mark.parametrize("costs", ["uniform", "varying"])
@pytest.mark.parametrize("technique", ["ss", "gss", "fac2", "tss"])
def test_device_report_is_the_per_claim_loops(technique, costs):
    """The columnar report plane reports what one call per claim did:
    equal ``to_dict()``, equal trace, and ``work_fn`` sees every chunk in
    grant order."""
    from repro.dls.report import SessionReport
    from repro.replay import Trace

    N, P = 160, 4
    c = TIMELINE_COSTS[costs](N)
    seen, want_seen = [], []
    s = dls.loop(N, technique, P=P, runtime="device")
    rep = dls.execute(s, lambda a, b: seen.append((a, b)),
                      executor="device", costs=c)
    sched = s.runtime.schedule
    oracle = dls.loop(N, technique, P=P, runtime="device")
    want = _report_by_claim_loop(oracle, sched, c,
                                 lambda a, b: want_seen.append((a, b)))
    assert seen == want_seen == [
        (a, a + k) for a, k in zip(sched.starts.tolist(),
                                   sched.sizes.tolist())]
    assert rep.to_dict() == want.to_dict()
    assert rep.to_json() == want.to_json()
    assert rep.busy_time.tobytes() == want.busy_time.tobytes()
    assert rep.per_pe_iters.dtype == want.per_pe_iters.dtype
    assert rep.steps == want.steps == sched.n_steps
    assert rep.claims == want.claims
    assert Trace.from_report(rep) == Trace.from_report(want)
    back = SessionReport.from_json(rep.to_json())
    assert back.to_dict() == rep.to_dict()
    assert back.claims == rep.claims


def test_a_device_drain_builds_no_claim_objects():
    """The drain logs its schedule as columns; the report's per-claim
    views are built on their first read."""
    from repro.dls.report import ClaimLog

    s = dls.loop(96, "ss", P=4, runtime="device")
    rep = s.execute(None, executor="device")
    for key in ("_per_pe_claims", "_chunk_times"):
        assert isinstance(rep.__dict__[key], ClaimLog)
    assert rep.steps == 96 == len(rep.chunk_times)
    assert isinstance(rep.__dict__["_per_pe_claims"], list)


def test_a_device_report_keeps_its_log_across_a_reset_and_a_drain():
    N, P = 120, 3
    costs = np.linspace(1.0, 2.0, N)
    s = dls.loop(N, "gss", P=P, runtime="device")
    first = s.execute(None, executor="device", costs=costs)
    want = _report_by_claim_loop(dls.loop(N, "gss", P=P, runtime="device"),
                                 s.runtime.schedule, costs).to_dict()
    s.reset()
    second = s.execute(None, executor="device", costs=np.ones(N))
    # the first report's views are built only now, after its log moved on
    assert first.to_dict() == want
    assert [c["t1"] for c in first.chunk_times] != \
        [c["t1"] for c in second.chunk_times]
    assert int(second.per_pe_iters.sum()) == N == len(
        {a for c in second.claims for a in range(c.start, c.stop)})


def test_a_session_with_host_claims_and_a_device_drain_reports_both():
    N, P = 150, 3
    s = dls.loop(N, "fac2", P=P, runtime="device")
    host = [s.claim(pe) for pe in (0, 2, 2)]
    s.record(2, host[1].size, 0.5, claim=host[1], t_start=0.0, t_end=0.5)
    rep = s.execute(None, executor="device")
    sched = s.runtime.schedule
    assert rep.per_pe_claims[0][0] is host[0]
    assert rep.per_pe_claims[2][:2] == host[1:]
    dev = [Claim(int(st), int(a), int(k)) for st, a, k in
           zip(sched.steps, sched.starts, sched.sizes)]
    assert sorted(rep.claims, key=lambda c: c.step) == host + dev
    assert int(rep.per_pe_iters.sum()) == N
    assert rep.steps == 3 + sched.n_steps
    assert rep.chunk_times[0] == {"pe": 2, "step": host[1].step,
                                  "start": host[1].start,
                                  "size": host[1].size, "t0": 0.0,
                                  "t1": 0.5, "lat": 0.0}
    assert len(rep.chunk_times) == 1 + sched.n_steps
    assert rep.busy_time[2] >= 0.5


# ---------------------------------------------------------------------------
# DeviceWindow: the Window contract over a device-array slab
# ---------------------------------------------------------------------------

def test_window_contract_semantics():
    w = DeviceWindow(capacity=16)
    assert w.fetch_add("k", 5) == 0  # returns the OLD value
    assert w.fetch_add("k", 3) == 5
    assert w.read("k") == 8
    w.reset("k", 41)
    assert w.read("k") == 41
    assert w.fetch_add("k", 1) == 41
    assert w.read("never-touched") == 0
    assert w.n_rmw == 3
    keys = ["k", "never-touched", "k"]
    assert w.read_many(keys) == [w.read(x) for x in keys]


def test_window_directory_is_append_only_and_bounded():
    w = DeviceWindow(capacity=2)
    assert w.slot("a") == 0
    assert w.slot("b") == 1
    assert w.slot("a") == 0  # published slots never move
    with pytest.raises(RuntimeError, match="directory full"):
        w.slot("c")


def test_window_adopt_validates_shape():
    import jax.numpy as jnp

    w = DeviceWindow(capacity=8)
    with pytest.raises(ValueError, match="adopted slab"):
        w.adopt(jnp.zeros(4, jnp.int32))
    w.adopt(jnp.arange(8, dtype=jnp.int32), n_rmw=6)
    assert w.read(w.keys()[0]) if w.keys() else True
    assert w.n_rmw == 6


def test_make_window_device_routes_through_availability():
    w = make_window("device", capacity=32)
    assert isinstance(w, DeviceWindow)
    assert w.capacity == 32
    assert w.capability_tier() in ("atomics", "aliased", "interpret")


def test_fetch_add_traced_shim_matches_host_path():
    import jax
    import jax.numpy as jnp

    w = DeviceWindow(capacity=8)

    @jax.jit
    def bump(d):
        return w.fetch_add_traced("ctr", d)

    olds = [int(bump(jnp.int32(2))) for _ in range(4)]
    assert olds == [0, 2, 4, 6]
    assert w.read("ctr") == 8  # same counter the host path sees
    assert w.fetch_add("ctr", 1) == 8


# ---------------------------------------------------------------------------
# DeviceRuntime: the one-sided protocol over the device window
# ---------------------------------------------------------------------------

def test_runtime_host_claims_match_plan():
    spec = host_spec("gss", 200, 4)
    rt = DeviceRuntime(spec)
    sizes, starts = plan(spec)
    got = []
    while True:
        c = rt.claim(0)
        if c is None:
            break
        got.append((c.start, c.size))
    assert got == list(zip(starts.tolist(), sizes.tolist()))
    assert rt.drained()


def test_runtime_rejects_adaptive_and_weighted():
    from repro.core.chunk_calculus import LoopSpec

    with pytest.raises(ValueError, match="no device closed form"):
        DeviceRuntime(LoopSpec("awf", N=100, P=2))
    with pytest.raises(ValueError, match="unweighted"):
        DeviceRuntime(LoopSpec("gss", N=100, P=2, weights=(1.0, 2.0)))


def test_runtime_rejects_foreign_window():
    from repro.core.rma import ThreadWindow

    with pytest.raises(TypeError, match="DeviceWindow"):
        DeviceRuntime(host_spec("gss", 100, 2), ThreadWindow())


# ---------------------------------------------------------------------------
# facade: dls.loop(runtime="device") + executor="device"
# ---------------------------------------------------------------------------

def test_device_session_end_to_end_and_replay_roundtrip():
    from repro.core.sim import simulate
    from repro.replay import Trace, calibrate, gantt_ascii

    N, P = 300, 4
    costs = np.linspace(1.0, 2.0, N)
    executed = []
    s = dls.loop(N, "gss", P=P, runtime="device")
    rep = dls.execute(s, lambda a, b: executed.append((a, b)),
                      executor="device", costs=costs)
    # coverage: the work_fn saw a partition of [0, N)
    cov = np.zeros(N, np.int64)
    for a, b in executed:
        cov[a:b] += 1
    assert (cov == 1).all()
    assert int(rep.per_pe_iters.sum()) == N
    assert s.runtime.drained()
    # protocol accounting: two RMWs per granted step (and the fast-path
    # reads are free -- they're device loads, not RMWs)
    assert rep.n_rmw_global == 2 * rep.steps
    assert rep.runtime == "one_sided"  # calibrates with the one-sided DES
    assert rep.executor == "device"
    assert rep.wall_time > 0
    # the capture plane round-trips unchanged
    tr = Trace.from_report(rep)
    assert tr.iters_covered() == N
    cal = calibrate(tr)
    r = simulate(cal.sim_config(seed=0))
    assert r.T_loop > 0
    assert "device" in gantt_ascii(tr) or tr.chunks  # renders without error


def test_device_session_serial_executor_interop():
    """Host-side claiming against the same device window still drains."""
    s = dls.loop(120, "tss", P=3, runtime="device", min_chunk=2)
    rep = dls.execute(s, None, executor="serial")
    assert int(rep.per_pe_iters.sum()) == 120
    assert s.runtime.drained()


def test_device_executor_requires_device_runtime():
    s = dls.loop(50, "ss", P=2)  # plain one-sided session
    with pytest.raises(ValueError, match='runtime="device"'):
        dls.execute(s, None, executor="device")


def test_loop_rejects_non_device_window_for_device_runtime():
    with pytest.raises(TypeError, match="DeviceWindow"):
        dls.loop(50, "ss", P=2, runtime="device", window="thread")


def test_device_hierarchy_composes():
    from repro.launch.mesh import make_device_hierarchy

    hw = make_device_hierarchy(capacity=64)
    assert isinstance(hw, HierarchicalWindow)
    s = dls.loop(90, "fac2", P=2, runtime="hierarchical", nodes=1, window=hw)
    rep = dls.execute(s, None, executor="serial")
    assert int(rep.per_pe_iters.sum()) == 90


# ---------------------------------------------------------------------------
# persistent compute kernels: self-scheduled == static, exactly
# ---------------------------------------------------------------------------

def test_mandelbrot_persistent_matches_static():
    from repro.kernels import mandelbrot, mandelbrot_persistent
    from repro.kernels.mandelbrot.persistent import mandelbrot_tile_costs

    ref = np.asarray(mandelbrot(64, 48, ct=30, block_h=16, block_w=16))
    out, sched = mandelbrot_persistent(
        64, 48, ct=30, block_h=16, block_w=16, technique="gss", workers=3)
    assert np.array_equal(np.asarray(out), ref)
    assert int(sched.sizes.sum()) == sched.N
    # the real per-tile cost model shapes the assignment, output unchanged
    costs = mandelbrot_tile_costs(ref, 16, 16)
    out2, sched2 = mandelbrot_persistent(
        64, 48, ct=30, block_h=16, block_w=16, technique="gss", workers=3,
        costs=costs)
    assert np.array_equal(np.asarray(out2), ref)
    # reusing a schedule skips the claim pass and stays exact
    out3, sched3 = mandelbrot_persistent(
        64, 48, ct=30, block_h=16, block_w=16, technique="gss", workers=3,
        schedule=sched2)
    assert sched3 is sched2
    assert np.array_equal(np.asarray(out3), ref)


@pytest.mark.slow
def test_mandelbrot_persistent_other_techniques():
    from repro.kernels import mandelbrot, mandelbrot_persistent

    ref = np.asarray(mandelbrot(96, 80, ct=60, block_h=32, block_w=32))
    for tech in ("fac2", "tss", "ss"):
        out, sched = mandelbrot_persistent(
            96, 80, ct=60, block_h=32, block_w=32, technique=tech, workers=3)
        assert np.array_equal(np.asarray(out), ref)
        assert int(sched.sizes.sum()) == sched.N


@pytest.mark.slow  # pallas compile-bound; the CI device job runs slow tier
def test_flash_attention_persistent_matches_static_causal():
    import jax
    import jax.numpy as jnp
    from repro.kernels import flash_attention, flash_attention_persistent

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    B, H, Hkv, T, D = 1, 2, 1, 32, 8
    q = jax.random.normal(kq, (B, H, T, D), jnp.float32)
    k = jax.random.normal(kk, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(kv, (B, Hkv, T, D), jnp.float32)
    ref = np.asarray(flash_attention(q, k, v, causal=True, blk_q=16, blk_k=16))
    out, _ = flash_attention_persistent(
        q, k, v, causal=True, blk_q=16, blk_k=16, technique="gss", workers=3)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


@pytest.mark.slow  # pallas compile-bound; the CI device job runs slow tier
def test_flash_attention_persistent_varlen_matches_oracle():
    import jax
    import jax.numpy as jnp
    from repro.kernels import attention_oracle, flash_attention_persistent

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    B, H, T, D = 2, 2, 32, 8
    q = jax.random.normal(kq, (B, H, T, D), jnp.float32)
    k = jax.random.normal(kk, (B, H, T, D), jnp.float32)
    v = jax.random.normal(kv, (B, H, T, D), jnp.float32)
    lengths = np.array([32, 19], np.int32)
    out, sched = flash_attention_persistent(
        q, k, v, causal=False, lengths=lengths, blk_q=16, blk_k=16,
        technique="fac2", workers=4)
    out = np.asarray(out)
    for b, L in enumerate(lengths):
        ref = np.asarray(attention_oracle(
            q[b:b + 1], k[b:b + 1, :, :L], v[b:b + 1, :, :L], causal=False))
        np.testing.assert_allclose(out[b], ref[0], atol=1e-5)
    # the cost model made short-batch tiles cheap: conservation still holds
    assert int(sched.sizes.sum()) == sched.N


def test_varlen_costs_reflect_lengths():
    from repro.kernels.flash_attention.persistent import varlen_tile_costs

    costs = varlen_tile_costs([64, 16], H=2, nq=4, blk_q=16, blk_k=16,
                              causal=True)
    assert costs.shape == (16,)
    # batch 0 (length 64): causal staircase 1,2,3,4 kv blocks per q block
    assert costs[:4].tolist() == [1, 2, 3, 4]
    # batch 1 (length 16): capped at one kv block everywhere
    assert costs[8:12].tolist() == [1, 1, 1, 1]
