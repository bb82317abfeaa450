"""Readings that the limits of a ``train_split`` cell are set from, taken
on the chips at the cell's own size, many seeds in one process
(``bench.calibrate``'s, for ``bench.drive_train_split``):

    python -m bench.calibrate_split --workload <cell> --seeds 1,2,3
                                    [--faults half_batch] [--control 0]

Per seed it prints one JSON line with the program's numbers (the lower
reading is the largest over seeds) and, unless ``--control 0``, the
control's (the reference in float8 put in the program's place; the upper
reading is the smallest). ``--faults`` also runs the program with a
fault from ``bench.faults`` planted and prints its numbers. The
benchmark's own runs do not run this.
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


def rows(cfg, mix, seeds, faults, control):
    from bench import faults as F
    from bench.drive_train import compare
    from bench.drive_train_split import SplitTrainCell

    refs = {}
    tc = SplitTrainCell(cfg, mix, seeds[0], Spans(annotate=False))
    for seed in seeds:
        if seed != tc.seed:
            tc.reseed(seed)
        prog = tc.first_steps()
        tc.free_program_state()
        ref = refs[seed] = tc.reference("f32")
        row = {"seed": seed, "program": compare(prog, ref),
               "losses": prog["losses"], "ref_losses": ref["losses"]}
        if control:
            row["control"] = compare(tc.reference("fp8"), ref)
        yield row
    del tc
    for name in faults:
        fc = SplitTrainCell(cfg, mix, seeds[0], Spans(annotate=False),
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
    ap.add_argument("--faults", default="")
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    _, cell, cfg, mix = load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    check_device(cell["chips"])
    for row in rows(cfg, mix, seeds, [f for f in args.faults.split(",") if f],
                    bool(args.control)):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
