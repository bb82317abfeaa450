"""Model (expert layer): the grouped matmul's share of its roofline (%).
A step's least time is the larger of its operations over the chip's
bf16 peak and its bytes over the HBM bandwidth (``bench.flops_moe``,
from the mean rows per step that the ``moe.rows`` counter gives); its
measured time is the device time under the model.moe.ffn scope in the
traced window over the steps that window holds (the window's length
over the median step time). Nothing where the program has no such scope
or no counter."""
from pathlib import Path

import numpy as np

from bench import flops_moe, scope_time, scopes
from bench.common import log

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    r = rec["record"]
    red = scopes.read_run(rec, ROOT)
    if red is None or not r.get("moe_rows") or not r.get("steps"):
        return None
    ffn_s = scope_time.under(rec, ROOT, "model.moe.ffn")
    if not ffn_s:
        return None
    cfg, pk, b = rec["cfg"], rec["peaks"], r["batch"]
    rows = float(np.mean([sum(s["held"]) for s in r["moe_rows"]]))
    step_s = float(np.median([s["t1"] - s["t0"] for s in r["steps"]]))
    t_f = flops_moe.ffn_flops(cfg, rows) / pk["bf16_flops_per_s"]
    t_b = flops_moe.ffn_bytes(cfg, r["layers"] * b["microbatches"],
                              rows) / pk["hbm_bytes_per_s"]
    log(f"roofline.moe_ffn.train: {rows:.0f} held rows a step, "
        f"{'bytes' if t_b > t_f else 'operations'} bound")
    return 100.0 * max(t_f, t_b) / (ffn_s * step_s / red["window_s"])
