"""Benchmark aggregator: one section per paper table/figure + system benches.

``python -m benchmarks.run``              -- quick mode (CI-friendly)
``python -m benchmarks.run --full``       -- paper-scale DES grids
``python -m benchmarks.run --list``       -- show the registry
``python -m benchmarks.run --only NAME``  -- run one benchmark (repeatable)

Prints ``name,us_per_call,derived`` CSV rows per the harness convention;
section headers are comment lines.
"""
from __future__ import annotations

import argparse
import time


def _table2(quick: bool) -> None:
    from benchmarks import table2_chunks

    table2_chunks.main(N=100_000 if quick else 1_000_000)


def _fig4(quick: bool) -> None:
    from benchmarks import fig4_psia

    fig4_psia.main(quick=quick)


def _fig5(quick: bool) -> None:
    from benchmarks import fig5_mandelbrot

    fig5_mandelbrot.main(quick=quick)


def _beyond(quick: bool) -> None:
    from benchmarks import beyond_paper

    beyond_paper.main()


def _overhead(quick: bool) -> None:
    from benchmarks import overhead

    overhead.main(quick=quick)


def _replay(quick: bool) -> None:
    from benchmarks import replay_predict

    replay_predict.main(quick=quick)


def _sim_sweep(quick: bool) -> None:
    from benchmarks import sim_sweep

    sim_sweep.main(quick=quick)


def _sim_fast(quick: bool) -> None:
    from benchmarks import sim_fast

    sim_fast.main(quick=quick)


def _kernels(quick: bool) -> None:
    from benchmarks import kernels_bench

    kernels_bench.main(quick=quick)


def _kernels_selfsched(quick: bool) -> None:
    from benchmarks import kernels_selfsched

    kernels_selfsched.main(quick=quick)


def _pt_contention(quick: bool) -> None:
    from benchmarks import pt_contention

    pt_contention.main(quick=quick)


def _serving_slo(quick: bool) -> None:
    from benchmarks import serving_slo

    serving_slo.main(quick=quick)


def _roofline(quick: bool) -> None:
    try:
        from benchmarks import roofline

        rows = roofline.load_all()
        if rows:
            print(roofline.table(rows))
        else:
            print("# no dry-run artifacts found; run "
                  "python -m repro.launch.dryrun --all first")
    except Exception as e:  # noqa: BLE001
        print(f"# roofline unavailable: {e}")


#: (name, section header, runner) -- selection surface for --list/--only.
BENCHMARKS = (
    ("table2", "Table 2: chunk calculus (closed form vs recurrence)", _table2),
    ("fig4_psia", "Fig. 4: PSIA DES grid (calibration in EXPERIMENTS.md)",
     _fig4),
    ("fig5_mandelbrot", "Fig. 5: Mandelbrot DES grid (qualitative claims)",
     _fig5),
    ("beyond_paper", "Beyond-paper techniques (TFSS / AWF / bounded chunks)",
     _beyond),
    ("overhead", "Scheduling overhead + scalability", _overhead),
    ("replay_predict",
     "Replay: predicted vs native + technique=auto selection", _replay),
    ("sim_sweep",
     "Batched sweeps: serial vs simulate_many on the predict roster",
     _sim_sweep),
    ("sim_fast",
     "Vectorized DES fast path vs event kernel (>=10x contended pin)",
     _sim_fast),
    ("kernels", "Kernels (interpret mode; see header caveat)", _kernels),
    ("kernels_selfsched",
     "Self-scheduled persistent grids vs static (device window protocol)",
     _kernels_selfsched),
    ("pt_contention",
     "pt: measured RMW latency / contention + DES prediction pin",
     _pt_contention),
    ("serving_slo",
     "Serving SLO: online re-selection vs fixed techniques under overload",
     _serving_slo),
    ("roofline", "Roofline (from dry-run artifacts, if present)", _roofline),
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grids (tens of minutes)")
    ap.add_argument("--list", action="store_true",
                    help="list registered benchmarks and exit")
    ap.add_argument("--only", action="append", metavar="NAME",
                    help="run only this benchmark (repeatable)")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    by_name = {name: (title, fn) for name, title, fn in BENCHMARKS}
    if args.list:
        width = max(len(n) for n in by_name)
        for name, title, _ in BENCHMARKS:
            print(f"{name:<{width}}  {title}")
        return 0
    selected = args.only if args.only else [n for n, _, _ in BENCHMARKS]
    unknown = [n for n in selected if n not in by_name]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; see --list")

    quick = not args.full
    t0 = time.time()
    for name in selected:
        title, fn = by_name[name]
        print(f"# === {title} ===")
        fn(quick)
    print(f"# total benchmark wall time: {time.time()-t0:.0f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
