"""Model, split training: model FLOPs of a whole step (``bench.flops``
for a dense configuration, ``bench.flops_moe`` for an expert share,
whose held rows come from the ``moe.rows`` counter, not from all
experts) times the window's steps per second, over the bf16 peak of the
chips the cell uses (%). Layers and chips come from the run's record."""
import numpy as np

from bench import flops, flops_moe


def read(rec):
    r = rec["record"]
    w = r.get("window")
    if not w or not w.get("steps") or "layers" not in r:
        return None
    cfg, b, layers = rec["cfg"], r["batch"], r["layers"]
    tokens = b["rows"] * b["seq"]
    if "router_experts" in cfg:
        if not r.get("moe_rows"):
            return None
        rows = float(np.mean([sum(s["held"]) for s in r["moe_rows"]]))
        per_step = flops_moe.train_flops(cfg, layers, tokens, b["seq"], rows)
    else:
        per_step = tokens * flops.train_flops_per_token(cfg, layers, b["seq"])
    rate = w["steps"] / (w["t1"] - w["t0"])
    peak = r["chips"] * rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_step * rate / peak
