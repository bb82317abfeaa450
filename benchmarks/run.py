"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract) and saves
full curves/tables under experiments/bench/.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,fig5] [--full]

``--smoke`` is the CI rot-detector mode: tiny episode/step counts so the
figure scripts execute end-to-end on CPU in minutes, with NO baseline
JSON writes (the CSV + per-run OUT_DIR artifacts are still emitted and
uploaded by the workflow).
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import BenchConfig, emit_csv_row

ALL = [
    "fig3_convergence",
    "fig4_algorithms",
    "fig5_monitoring",
    "fig6_eavesdroppers",
    "fig7_exploration",
    "fig8_no_location",
    "fig9_example",
    "fig10_leakage_attack",
    "table_power",
    "throughput",
    "pipeline",
    "serving",
    "zoo_plan_scoring",
]


def select(names, only: str):
    """Resolve a ``--only`` spec against the benchmark list.

    A spec entry matches a benchmark on its EXACT name or as an explicit
    underscore-delimited prefix (``fig10`` -> ``fig10_leakage_attack``).
    Bare ``startswith`` matching would make ``--only fig1`` silently run
    ``fig10_leakage_attack``; an entry that matches nothing is an error
    rather than a silent no-op.
    """
    picked = []
    for o in only.split(","):
        o = o.strip()
        if not o:
            continue
        hits = [n for n in names if n == o or n.startswith(o + "_")]
        if not hits:
            raise SystemExit(f"--only: {o!r} matches no benchmark in {names}")
        picked.extend(h for h in hits if h not in picked)
    return [n for n in names if n in picked]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated benchmark names")
    ap.add_argument("--full", action="store_true", help="paper-scale episode counts")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny counts, no baseline JSON writes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--leakage", default="analytic",
                    choices=("analytic", "empirical"),
                    help="leakage model the fig benchmarks price hops "
                         "with: the paper's closed-form values or the "
                         "trained attacker population's measurements")
    args = ap.parse_args(argv)

    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    bench = BenchConfig(quick=not args.full, smoke=args.smoke,
                        leakage=args.leakage)
    names = ALL if not args.only else select(ALL, args.only)
    print("name,us_per_call,derived")
    t_all = time.time()
    failures = []
    for name in names:
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        t0 = time.time()
        try:
            mod.main(bench, seed=args.seed)
            emit_csv_row(f"{name}/walltime", (time.time() - t0) * 1e6, "ok")
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            emit_csv_row(f"{name}/walltime", (time.time() - t0) * 1e6, f"FAIL: {e}")
    emit_csv_row("total/walltime", (time.time() - t_all) * 1e6,
                 f"{len(names) - len(failures)}/{len(names)} benchmarks ok")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    main()
