"""Readings that the limits in ``bench/limits/<cell>.json`` are set from,
taken on the chip at the cell's own size, many seeds in one process:

    python -m bench.calibrate --workload <cell> --seeds 1,2,3 [--seconds 20]
                              [--faults half_batch]

Per seed it prints one JSON line with the program's numbers (the lower
reading is the largest over seeds) and the control's (the reference in
float8 put in the program's place; the upper reading is the smallest).
Serving runs a short open-loop window at the cell's own load; training
needs no window. ``--faults`` also runs the program with a fault from
``bench.faults`` planted and prints its numbers. The benchmark's own runs
do not run this.
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

from bench.common import Spans, load_cell  # noqa: E402
from bench.run import check_device  # noqa: E402


def serve(cfg, mix, seeds, seconds):
    from bench.drive_serve import ServeCell

    sc = ServeCell(cfg, mix, seeds[0], Spans(annotate=False))
    sc.warm()
    for seed in seeds:
        sc.reseed(seed)
        w = sc.window(seconds)
        sc.free_program_state()
        chk = sc.check(control=True)
        yield {"seed": seed, "program": {"served_gap": chk["served_gap"]},
               "control": {"served_gap": chk["control_gap"]},
               "checked_requests": chk["checked_requests"],
               "checked_slots": len({sc.slot_of[r] for r in sc.sample()}),
               "checked_tokens": chk["checked_tokens"],
               "completed": w["completed"], "due": w["attempted"]}


def train(cfg, mix, seeds, faults):
    from bench import faults as F
    from bench.drive_train import TrainCell, compare

    refs = {}
    tc = TrainCell(cfg, mix, seeds[0], Spans(annotate=False))
    for seed in seeds:
        if seed != tc.seed:
            tc.reseed(seed)
        prog = tc.first_steps()
        tc.free_program_state()
        ref = refs[seed] = tc.reference("f32")
        ctl = tc.reference("fp8")
        yield {"seed": seed, "program": compare(prog, ref),
               "control": compare(ctl, ref), "losses": prog["losses"],
               "ref_losses": ref["losses"]}
    del tc
    for name in faults:
        fc = TrainCell(cfg, mix, seeds[0], Spans(annotate=False),
                       hooks={"train_step": F.TRAIN[name]})
        for seed in seeds:
            if seed != fc.seed:
                fc.reseed(seed)
            prog = fc.first_steps()
            fc.free_program_state()
            yield {"seed": seed, "fault": name,
                   "program": compare(prog, refs[seed])}
        del fc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    _, cell, cfg, mix = load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    check_device(cell["chips"])
    if mix["path"] == "serve":
        rows = serve(cfg, mix, seeds, args.seconds)
    else:
        rows = train(cfg, mix, seeds,
                     [f for f in args.faults.split(",") if f])
    for row in rows:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
