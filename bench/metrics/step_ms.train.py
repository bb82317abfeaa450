"""Split executor: median time from one step's loss on the host to the
next's in the window (ms); steps are dispatched ahead, so this is the
device's step time while the host keeps up."""
import numpy as np


def read(rec):
    d = [s["t1"] - s["t0"] for s in rec["record"].get("steps", [])]
    return float(np.median(d)) * 1e3 if d else None
