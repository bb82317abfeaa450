"""Model, training: model FLOPs of forward and backward per token
(``bench.flops.train_flops_per_token``) times the window's tokens per
second, over the chip's bf16 peak (%)."""
from bench import flops


def read(rec):
    w = rec["record"].get("window")
    if not w or not w.get("steps"):
        return None
    cfg, b = rec["cfg"], rec["record"]["batch"]
    per_tok = flops.train_flops_per_token(cfg, cfg["train_layers"], b["seq"])
    rate = w["tokens"] / (w["t1"] - w["t0"])
    return 100.0 * per_tok * rate / rec["peaks"]["bf16_flops_per_s"]
