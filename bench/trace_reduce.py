"""Reduce a profiler trace of one window to what the benchmark reports.

``load`` reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
keeps two things: each device's operations (planes ``/device:TPU:<n>``,
line ``XLA Ops``: name, start, end) and the benchmark's host spans
(events named ``bench.*`` on the host planes). ``reduce`` takes that and

* clips everything to the ``bench.window`` span;
* busy time per device: the union of its operations' intervals; the
  reported ``busy_s`` is the mean over devices, ``window_s`` the window;
* ``device_ops``: the ten operations with the most self time (an
  operation's time less that of the operations nested in it, as a
  ``while`` holds its body), averaged over devices; an operation is named
  by its HLO instruction (``fusion.329``), not its full text;
* ``idle_gaps``: the ten longest gaps in device 0's busy union, each
  named by the innermost bench span that covers it (``in bench.tick``),
  or by the span that ended last before it (``after bench.tick``).

The events in between are plain lists, so a test can feed ``reduce`` a
small recorded window without the profiler.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."


def load(xplane: Path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(short(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def short(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def _self_times(iv, acc, weight):
    """Add each operation's self time to ``acc``; ``iv`` holds clipped
    ``(start, end, name)`` of one device, nested as a tree."""
    stack = []
    for s, e, op in sorted(iv, key=lambda x: (x[0], -x[1])):
        # an operation that ends past the one open above it is its sibling
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            stack.pop()
        if stack:
            acc[stack[-1][2]] -= (e - s) * weight
        acc[op] += (e - s) * weight
        stack.append((s, e, op))


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(ev: dict, top: int = 10) -> dict:
    win = [s for s in ev["spans"] if s[0] == SPAN_PREFIX + "window"]
    if not win or not ev["devices"]:
        raise ValueError("trace has no bench.window span or no device ops")
    w0, w1 = win[0][1], win[0][2]
    n_dev = len(ev["devices"])
    busy, per_op, first_union = [], defaultdict(float), None
    for name in sorted(ev["devices"]):
        iv = [(max(s, w0), min(e, w1), op) for op, s, e in ev["devices"][name]
              if e > w0 and s < w1]
        _self_times(iv, per_op, 1.0 / n_dev)
        u = _union([(s, e) for s, e, _ in iv])
        busy.append(sum(e - s for s, e in u))
        if first_union is None:
            first_union = u
    edges = [w0] + [x for s, e in first_union for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in ev["spans"] if s[0] != SPAN_PREFIX + "window"]
    longest = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:top]
    ops = sorted(per_op.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "busy_s": sum(busy) / n_dev * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": [[op, t * 1e-9] for op, t in ops],
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) * 1e-9]
                      for g in longest],
    }


def _label(gap, spans) -> str:
    mid = (gap[0] + gap[1]) / 2
    cover = [s for s in spans if s[1] <= mid <= s[2]]
    if cover:
        return "in " + min(cover, key=lambda s: s[2] - s[1])[0]
    before = [s for s in spans if s[2] <= mid]
    if before:
        return "after " + max(before, key=lambda s: s[2])[0]
    return "before any bench span"


def reduce_dir(trace_dir: Path) -> dict:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` output directory."""
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(load(files[-1]))
