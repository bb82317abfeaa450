"""Device, training: 1 - busy / window over the traced window (%)."""


def read(rec):
    t = rec.get("trace")
    return 100.0 * (1 - t["busy_s"] / t["window_s"]) if t else None
