"""Find a cell's configuration, traffic mix, driver and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under ``chipbench/``:

  configs/<config>.json     the configuration as it is run
  traffic/<traffic>.json    the parameters of a traffic mix
  drivers/<entry>.py        the entry point a configuration names: its
                            drains, kernels, control precision and the
                            shapes ``rehearse.py`` compiles
  metrics/<metric>.py       one reader per per-layer metric

so a later change adds a cell, a configuration or a metric by adding
files and entries to ``BENCHMARK.json``, and edits none of these.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(name: str, bench: dict | None = None) -> dict:
    bench = load_benchmark() if bench is None else bench
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")


def config_entry(name: str, bench: dict) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: dict | None = None):
    """(configuration, traffic) dicts of the workload ``name``."""
    bench = load_benchmark() if bench is None else bench
    w = workload(name, bench)
    cfg = _json(ROOT / config_entry(w["config"], bench)["file"])
    traffic = _json(HERE / "traffic" / f"{w['traffic']}.json")
    return cfg, traffic


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(entry: str):
    """The module ``drivers/<entry>.py``."""
    return _module(HERE / "drivers" / f"{entry}.py", f"chipbench_driver_{entry}")


def metric_reader(metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    mod = _module(HERE / "metrics" / f"{metric}.py",
                  f"chipbench_metric_{metric.replace('.', '_')}")
    return mod.read


def per_layer_for(name: str, bench: dict) -> list:
    """The per-layer metrics that the workload ``name`` reports."""
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name])]


def end_to_end_for(name: str, bench: dict) -> list:
    return [m for m in bench["end_to_end"]
            if name in m.get("workloads", [name])]
