"""Serving host loop: median host time from the end of one ``bench.tick``
to the start of the next while a slot is active (ms)."""
import numpy as np


def read(rec):
    ticks = rec["record"].get("ticks", [])
    gaps = [b["t0"] - a["t1"] for a, b in zip(ticks, ticks[1:])
            if a["active_after"] > 0]
    return float(np.median(gaps)) * 1e3 if gaps else None
