"""Model, decode: roofline share of the ticks that admitted none. A
tick's least time is the larger of its required FLOPs over peak FLOP/s
and its required bytes over peak bytes/s: the weights once per decode
step in which a slot was active, plus each active slot's cache up to its
own position (``bench.flops``). The share is the sum of least times over
the sum of measured tick times (%). Which bound binds is printed."""
from bench import flops
from bench.common import log


def read(rec):
    cfg, w = rec["cfg"], rec["record"]["window"]
    ticks = [k for k in rec["record"].get("ticks", []) if not k["admitted"]]
    if not ticks:
        return None
    pk = rec["peaks"]
    layers = cfg["num_hidden_layers"]
    width = 2  # bf16
    wb = flops.weight_bytes(cfg, layers, width)
    kvb = flops.kv_bytes_per_token(cfg, layers, width)
    least, took, by_bytes = 0.0, 0.0, 0
    for k in ticks:
        f = b = 0.0
        for rid, before, steps in k["slots"]:
            p = w["plen"][rid]
            for i in range(before, before + steps):
                f += flops.decode_flops(cfg, layers, p + i)
                b += kvb * (p + i)
        b += wb * max((s for _, _, s in k["slots"]), default=0)
        t_f, t_b = f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"]
        least += max(t_f, t_b)
        by_bytes += t_b >= t_f
        took += k["t1"] - k["t0"]
    log(f"mfu.decode: bytes bound binds in {by_bytes} of {len(ticks)} "
        f"decode ticks")
    return 100.0 * least / took
