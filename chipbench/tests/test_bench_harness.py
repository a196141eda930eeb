"""The harness's refusals and its traffic generator, on the CPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import cells, traffic

ROOT = Path(__file__).resolve().parents[2]
CELL = "mandel-1152-ct1000-gss"


def _cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_without_a_chip_it_fails_and_prints_no_result():
    r = _cli(ROOT, {"PYTHONPATH": str(ROOT / "src")})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_every_workload_finds_its_files():
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        cfg, tr = cells.load_cell(w["name"], bench)
        assert set(cfg["limits"])
        assert cells.per_layer_for(w["name"], bench)
        assert {m["name"] for m in cells.end_to_end_for(w["name"], bench)} \
            == {"drain_ms", "drain_p95_ms", "setup_s"}
    for m in bench["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_every_seed_gets_the_same_batches(seed):
    tr = cells.load_cell("attn-dsk67-tp8-varlen")[1]
    want = traffic.pool_lengths(tr, 1)
    got = traffic.pool_lengths(tr, seed)
    assert got.shape == (tr["pool"], tr["batch"])
    key = lambda b: sorted(map(sorted, b.tolist()))  # noqa: E731
    assert key(got) == key(want)
    assert got.min() >= tr["lengths"]["min"]
    assert got.max() <= tr["lengths"]["max"]
    assert np.array_equal(got, traffic.pool_lengths(tr, seed))


def test_lognormal_lengths_have_the_stated_median():
    spec = {"dist": "lognormal", "median": 1024, "sigma": 1.0, "min": 64,
            "max": 4096}
    L = traffic.lengths_multiset(spec, 96)
    assert np.median(L) == pytest.approx(1024, rel=0.05)
    assert list(L) == sorted(L)


def test_reservoir_keeps_a_seeded_sample_of_k():
    def sample(seed):
        r = traffic.Reservoir(3, seed)
        for i in range(50):
            r.offer(i)
        return sorted(r.kept)

    assert len(sample(1)) == 3 and sample(1) == sample(1)
    assert any(sample(s) != sample(1) for s in range(2, 8))
