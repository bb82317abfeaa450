"""Model, prefill: the FLOPs the admitted prompts require at their real
lengths (``bench.flops.prefill_flops``, logits of the last token only),
summed over the admitting ticks, over those ticks' time, over the chip's
bf16 peak (%)."""
from bench import flops


def read(rec):
    cfg, w = rec["cfg"], rec["record"]["window"]
    ticks = [k for k in rec["record"].get("ticks", []) if k["admitted"]]
    if not ticks:
        return None
    layers = cfg["num_hidden_layers"]
    need = sum(flops.prefill_flops(cfg, layers, w["plen"][r])
               for k in ticks for r in k["admitted"])
    took = sum(k["t1"] - k["t0"] for k in ticks)
    return 100.0 * need / took / rec["peaks"]["bf16_flops_per_s"]
