"""Serving engine: median duration of the ticks that admitted a request
(prefill plus the tick's decode chunk), by the ``bench.tick`` spans (ms)."""
import numpy as np


def read(rec):
    d = [k["t1"] - k["t0"] for k in rec["record"].get("ticks", [])
         if k["admitted"]]
    return float(np.median(d)) * 1e3 if d else None
