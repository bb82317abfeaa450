"""Find a serving cell's knee once, on the chip: one process, one compile,
then a short open-loop window at each offered rate.

    python -m bench.sweep --workload <cell> --rates 1,2,4,6 --seconds 20

For each rate it prints the offered and completed request rates, the
median and 95th-percentile time to first token, and the drain: how long
after the last due time the last request finished. Past the knee the
queue grows through the window, so the drain and the TTFT tail grow with
the window; below it they stay flat. The cell's traffic file then fixes
its rate at about four fifths of the knee. The benchmark's own runs do
not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.common import Spans, load_cell, quantile  # noqa: E402
from bench.run import check_device  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    _, cell, cfg, mix = load_cell(args.workload)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    check_device(cell["chips"])
    from bench.drive_serve import ServeCell

    sc = ServeCell(cfg, mix, args.seed, Spans(annotate=False))
    sc.warm()
    for rate in [float(r) for r in args.rates.split(",")]:
        mix["arrivals"]["rate_per_s"] = rate
        sc.svc.state = sc._fresh()
        w = sc.window(args.seconds)
        last_due = max(r.due for r in sc.reqs.values())
        drain = w["window_t1"] - (w["window_t0"] + last_due)
        span = w["window_t1"] - w["window_t0"]
        print(json.dumps({
            "rate": rate, "due": w["attempted"], "completed": w["completed"],
            "completed_per_s": w["completed"] / span,
            "ttft_p50_ms": 1e3 * quantile(w["ttft_s"], 0.5),
            "ttft_p95_ms": 1e3 * quantile(w["ttft_s"], 0.95),
            "tpot_p95_ms": 1e3 * quantile(w["tpot_s"], 0.95)
            if w["tpot_s"] else None,
            "drain_s": drain, "ticks": w["ticks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
