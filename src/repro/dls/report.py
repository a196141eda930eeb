"""Per-session scheduling metrics (the paper's Sec. 5 measurands).

Every claim a ``DLSession`` hands out is logged per PE; execution feedback
(``session.record``) accumulates per-PE busy time.  ``SessionReport``
aggregates both into the quantities the paper reports: number of
scheduling steps, chunk-size series, per-PE iteration counts, and the
load-imbalance coefficient of variation of per-PE busy/finish times.

A session logs its claims two ways: one at a time from the host
executors, and a whole device schedule at once as columns
(``ScheduleColumns``).  A report's ``per_pe_claims`` and ``chunk_times``
are built from that log when first read, and then kept.

Reports are persistable: ``to_json()``/``from_json()`` round-trip every
field under an explicit ``schema_version`` -- the ``repro.replay`` trace
store is built on the per-chunk timing (``chunk_times``) carried here, so
a recorded run can be replayed/calibrated long after the session is gone
(DESIGN.md Sec. 9).
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import List, Optional, Sequence

import numpy as np

from repro.core.scheduler import Claim
from repro.core.weights import coefficient_of_variation

#: Version of the serialized-report schema (``to_json``).  Bump on any
#: backward-incompatible field change; ``from_json`` rejects newer majors.
REPORT_SCHEMA_VERSION = 1


class _BuiltWhenRead:
    """A ``SessionReport`` field that may be given the session's
    ``ClaimLog``: the field's view of it is built on the first read and
    kept in its place."""

    def __init__(self, default=dataclasses.MISSING):
        self.default = default

    def __set_name__(self, owner, name):
        self.name, self.key = name, "_" + name

    def __get__(self, obj, owner=None):
        if obj is None:  # the field's default; none makes it required
            if self.default is dataclasses.MISSING:
                raise AttributeError(self.name)
            return self.default
        value = obj.__dict__[self.key]
        if isinstance(value, ClaimLog):
            value = obj.__dict__[self.key] = getattr(value, self.name)()
        return value

    def __set__(self, obj, value):
        obj.__dict__[self.key] = value


@dataclasses.dataclass(frozen=True, eq=False)  # arrays: no value equality
class ScheduleColumns:
    """A device schedule's claims as columns, one row a claim, in grant
    order, with its modeled timeline ``t0``/``t1``.

    ``done`` lists the rows in completion order (by t0, t1, worker; ties
    in grant order): the order of their ``chunk_times`` records.
    """

    pe: np.ndarray
    step: np.ndarray
    start: np.ndarray
    size: np.ndarray
    t0: np.ndarray
    t1: np.ndarray
    done: np.ndarray

    @classmethod
    def of(cls, workers, steps, starts, sizes, t0s, t1s) -> "ScheduleColumns":
        cols = dict(pe=workers, step=steps, start=starts, size=sizes)
        cols = {k: np.array(v, np.int64) for k, v in cols.items()}
        cols.update(t0=np.array(t0s, np.float64), t1=np.array(t1s, np.float64))
        cols["done"] = np.lexsort((cols["pe"], cols["t1"], cols["t0"]))
        for v in cols.values():
            v.flags.writeable = False  # a report shares them
        return cls(**cols)

    def add_busy(self, busy: np.ndarray) -> None:
        """Add each claim's t1 - t0 to its PE's ``busy``, one at a time in
        completion order (so the float sums are those of a per-claim
        loop)."""
        d = self.done
        np.add.at(busy, self.pe[d], self.t1[d] - self.t0[d])

    def add_to(self, claims: List[List[Claim]], times: List[dict]) -> None:
        for pe, step, start, size in zip(self.pe.tolist(), self.step.tolist(),
                                         self.start.tolist(),
                                         self.size.tolist()):
            claims[pe].append(Claim(step=step, start=start, size=size))
        d = self.done
        times.extend(
            {"pe": pe, "step": step, "start": start, "size": size,
             "t0": t0, "t1": t1, "lat": 0.0}
            for pe, step, start, size, t0, t1 in zip(
                self.pe[d].tolist(), self.step[d].tolist(),
                self.start[d].tolist(), self.size[d].tolist(),
                self.t0[d].tolist(), self.t1[d].tolist()))

    def iters(self, P: int) -> np.ndarray:
        return np.bincount(self.pe, weights=self.size,
                           minlength=P).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class HostClaims:
    """Claims (per PE) and chunk timings logged one at a time."""

    claims: List[List[Claim]]
    times: List[dict]

    def add_to(self, claims: List[List[Claim]], times: List[dict]) -> None:
        for pe, per in enumerate(self.claims):
            claims[pe].extend(per)
        times.extend(self.times)

    def iters(self, P: int) -> np.ndarray:
        return np.array([sum(c.size for c in per) for per in self.claims]
                        + [0] * (P - len(self.claims)), dtype=np.int64)


@dataclasses.dataclass(frozen=True)
class ClaimLog:
    """A session's claim log as a report holds it: parts in log order."""

    parts: Sequence  # of HostClaims | ScheduleColumns
    P: int

    def iters(self) -> np.ndarray:
        return sum((part.iters(self.P) for part in self.parts),
                   np.zeros(self.P, np.int64))

    @functools.cached_property
    def _views(self):
        claims: List[List[Claim]] = [[] for _ in range(self.P)]
        times: List[dict] = []
        for part in self.parts:
            part.add_to(claims, times)
        return claims, times

    def per_pe_claims(self) -> List[List[Claim]]:
        return self._views[0]

    def chunk_times(self) -> Optional[List[dict]]:
        return self._views[1] or None


@dataclasses.dataclass
class SessionReport:
    """Aggregated metrics for one (possibly partial) session execution."""

    technique: str
    N: int
    P: int
    runtime: str  # "one_sided" | "two_sided" | "hierarchical"
    executor: Optional[str]  # "serial"|"threads"|"processes"|"sim"|None (manual)
    per_pe_claims: List[List[Claim]] = _BuiltWhenRead()
    per_pe_iters: np.ndarray  # iterations executed (sim) or claimed, per PE
    busy_time: np.ndarray  # seconds of work_fn execution per PE
    wall_time: float  # wall-clock of execute() (sim: virtual T_loop)
    # Chunk bounds of the spec that produced this report: without them a
    # replayed/predicted schedule would silently use default bounds.
    min_chunk: int = 1
    max_chunk: Optional[int] = None
    n_claims: Optional[int] = None  # overrides len(claims) (sim executor)
    # Per-level RMW counts (the follow-up paper's headline metric): how many
    # window RMWs paid the global serialization point vs a node-local one.
    # None when the window backend does not account (plain one-sided
    # ThreadWindow); flat sessions over counting windows report local=0.
    n_rmw_global: Optional[int] = None
    n_rmw_local: Optional[int] = None
    # Adaptation trace (adaptive policies only, DESIGN.md Sec. 8): the
    # policy's weight-update history -- for the AWF variants one entry per
    # update boundary ({"update": ordinal, "weights": [per-PE]}), for AF
    # one per recorded chunk ({"update", "pe", "mu"}).  None for static
    # policies; capped at the policy's trace_limit.
    adaptation: Optional[List[dict]] = None
    # Per-chunk timing (the repro.replay data plane, DESIGN.md Sec. 9):
    # one dict per executed chunk -- {"pe", "step", "start", "size", "t0",
    # "t1", "lat"} with t0/t1 seconds since execute() began (the DES's
    # virtual clock for executor="sim") and lat the claim latency.  None
    # when the session was driven without timestamps (manual claim loops).
    chunk_times: Optional[List[dict]] = _BuiltWhenRead(default=None)
    # technique="auto" only: the selection record -- chosen technique,
    # predicted ranking (ordered sweep of simulated T_loop), seed, budget,
    # and workload source.  None for explicitly chosen techniques.
    auto_decision: Optional[dict] = None
    # executor="processes" only (repro.pt): start method, atomicity
    # backend ("atomics"/"lockf"), per-PE process stats (pid, chunks,
    # RMW counts, death/salvage/orphan accounting), and the orphan
    # hand-off log.  None for in-process executors.
    process_stats: Optional[dict] = None
    # Serving scenarios only (repro.serve.scenarios): the SLO slice this
    # session (= one admission epoch) contributed -- an ``SLOReport``
    # dict -- and the online re-selection decisions taken at this
    # epoch's boundary (full predicted ranking included).  None outside
    # the serving plane.
    slo: Optional[dict] = None
    reselections: Optional[List[dict]] = None

    @property
    def claims(self) -> List[Claim]:
        return [c for per in self.per_pe_claims for c in per]

    @property
    def chunk_sizes(self) -> List[int]:
        return [c.size for c in self.claims]

    @property
    def total_iters(self) -> int:
        return int(self.per_pe_iters.sum())

    @property
    def steps(self) -> int:
        n = len(self.claims) if self.n_claims is None else self.n_claims
        return n

    @property
    def cov(self) -> float:
        """Load imbalance: c.o.v. of per-PE busy time (lower = better)."""
        if self.busy_time.sum() <= 0:
            return 0.0
        return coefficient_of_variation(self.busy_time)

    @property
    def n_weight_updates(self) -> int:
        """How many times the weight policy adapted during this session."""
        return len(self.adaptation) if self.adaptation else 0

    def final_weights(self) -> Optional[List[float]]:
        """The last adapted per-PE weights (AWF variants), if any."""
        if not self.adaptation:
            return None
        for entry in reversed(self.adaptation):
            if "weights" in entry:
                return entry["weights"]
        return None

    def summary(self) -> str:
        rmw = ""
        if self.n_rmw_global is not None:
            rmw = f" rmw_g={self.n_rmw_global}"
            if self.n_rmw_local is not None:
                rmw += f" rmw_l={self.n_rmw_local}"
        if self.adaptation:
            rmw += f" adapt={self.n_weight_updates}"
        if self.auto_decision:
            rmw += f" auto->{self.auto_decision.get('chosen')}"
        if self.process_stats:
            ps = self.process_stats
            rmw += (f" procs[{ps.get('start_method')}/"
                    f"{ps.get('window_backend')}"
                    f"{' deaths=' + str(ps['n_deaths']) if ps.get('n_deaths') else ''}]")
        return (
            f"{self.technique} N={self.N} P={self.P} [{self.runtime}"
            f"{'/' + self.executor if self.executor else ''}] "
            f"steps={self.steps} iters={self.total_iters} "
            f"cov={self.cov:.3f} wall={self.wall_time:.3f}s{rmw}"
        )

    # ------------------------------------------------------------------
    # persistence (schema-versioned; the replay trace store depends on it)
    # ------------------------------------------------------------------
    def to_dict(self) -> dict:
        """Plain-JSON representation (claims as [step, start, size])."""
        return {
            "schema_version": REPORT_SCHEMA_VERSION,
            "technique": self.technique,
            "N": self.N,
            "P": self.P,
            "runtime": self.runtime,
            "executor": self.executor,
            "per_pe_claims": [[[c.step, c.start, c.size] for c in per]
                              for per in self.per_pe_claims],
            "per_pe_iters": [int(x) for x in self.per_pe_iters],
            "busy_time": [float(x) for x in self.busy_time],
            "wall_time": float(self.wall_time),
            "min_chunk": self.min_chunk,
            "max_chunk": self.max_chunk,
            "n_claims": self.n_claims,
            "n_rmw_global": self.n_rmw_global,
            "n_rmw_local": self.n_rmw_local,
            "adaptation": self.adaptation,
            "chunk_times": self.chunk_times,
            "auto_decision": self.auto_decision,
            "process_stats": self.process_stats,
            "slo": self.slo,
            "reselections": self.reselections,
        }

    def to_json(self, indent: Optional[int] = None) -> str:
        """Canonical JSON text (sorted keys, so equal reports serialize
        byte-identically -- the trace store's round-trip contract)."""
        return json.dumps(self.to_dict(), sort_keys=True, indent=indent,
                          separators=(",", ":") if indent is None else None)

    @classmethod
    def from_dict(cls, d: dict) -> "SessionReport":
        ver = d.get("schema_version")
        if ver is None or ver > REPORT_SCHEMA_VERSION:
            raise ValueError(
                f"unsupported SessionReport schema_version {ver!r} "
                f"(this build reads <= {REPORT_SCHEMA_VERSION})")
        return cls(
            technique=d["technique"],
            N=d["N"],
            P=d["P"],
            runtime=d["runtime"],
            executor=d.get("executor"),
            per_pe_claims=[[Claim(step=c[0], start=c[1], size=c[2])
                            for c in per]
                           for per in d["per_pe_claims"]],
            per_pe_iters=np.asarray(d["per_pe_iters"], dtype=np.int64),
            busy_time=np.asarray(d["busy_time"], dtype=np.float64),
            wall_time=float(d["wall_time"]),
            min_chunk=int(d.get("min_chunk", 1)),
            max_chunk=d.get("max_chunk"),
            n_claims=d.get("n_claims"),
            n_rmw_global=d.get("n_rmw_global"),
            n_rmw_local=d.get("n_rmw_local"),
            adaptation=d.get("adaptation"),
            chunk_times=d.get("chunk_times"),
            auto_decision=d.get("auto_decision"),
            process_stats=d.get("process_stats"),
            slo=d.get("slo"),
            reselections=d.get("reselections"),
        )

    @classmethod
    def from_json(cls, text: str) -> "SessionReport":
        return cls.from_dict(json.loads(text))
