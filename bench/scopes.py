"""Device time by the program's named scopes, idle time by host span.

The program labels its device work with ``jax.named_scope`` (``engine.*``,
``model.*``, ``pipeline.*``, ``optim.*``) and, with a tracer on, its
host loop with ``serve.*`` and ``host.*`` spans. A profiler trace keeps the
scopes only in each compiled program's HLO (the ``op_name`` metadata of its
instructions, in the ``Hlo Proto`` stats of the ``/host:metadata`` plane);
a device operation names its instruction, and the ``XLA Modules`` line
says which program ran it. ``load`` joins the two:

* ``devices``: per device, ``(instruction, start, end)`` as in
  ``bench.trace_reduce.load``; ``paths``: beside each, its ``op_name``
  (``""`` when the instruction has none, as for copies XLA inserts);
* ``spans``: host spans named ``bench.*``, ``serve.*`` or ``host.*``.

``reduce`` clips to the ``bench.window`` span and gives

* ``scopes``: per scope name, the self time (``trace_reduce``'s: nested
  operations subtracted) of the operations whose path holds it, averaged
  over devices. Inclusive: ``model.kv_write`` time also counts under the
  ``engine.decode`` around it. An operation with no path takes the path of
  the innermost operation that encloses it on its device's timeline (a
  copy inside the decode ``while`` takes the loop's); what is left goes to
  ``unscoped``. Each operation's outermost scope, plus ``unscoped``, sums
  to the device's operation time (its busy time when operations nest);
* ``scopes_own``: per scope name, the self time of the operations whose
  innermost scope it is (``model.layers`` less the ``model.block`` inside
  it: the layer scan's own slicing and stacking);
* ``scope_paths``: the ten paths (``op_name`` less the operation) with the
  most self time;
* ``idle_by_span``: device 0's idle time in the window, each gap under the
  innermost ``serve.*``/``host.*`` span over its middle, else under
  ``trace_reduce``'s bench label (``in bench.tick``, ``after bench.tick``).

``read_run(rec, root)`` finds the trace a ``--trace 1`` run has just
written under ``root/.bench_out/trace/`` and reduces it once; the
per-layer readers call it and ``share`` turns it into a percentage.
``python -m bench.scopes <trace dir or .xplane.pb>`` prints the
reduction.
"""
from __future__ import annotations

import functools
import json
import re
import sys
import time
from collections import defaultdict
from pathlib import Path

from bench import trace_reduce as T

SPAN_PREFIXES = ("bench.", "serve.", "host.")
PROGRAM_SPANS = ("serve.", "host.")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
# a scope is a path component such as engine.decode (also inside
# transpose(jvp(pipeline.head))); argument names (state.active) appear
# only as the last component, which is the operation
_SCOPE = re.compile(r"[a-z]+\.[a-z_]+")

# the parts of xplane.proto and hlo.proto this reads; protobuf skips the rest
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False),
               ("event_metadata", 4, "EventMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStat": [("bytes_value", 6, "bytes", False)],
    "HloProto": [("hlo_module", 1, "HloModule", False)],
    "HloModule": [("computations", 3, "HloComputation", True)],
    "HloComputation": [("instructions", 2, "HloInstruction", True)],
    "HloInstruction": [("name", 1, "string", False),
                       ("metadata", 7, "OpMetadata", False)],
    "OpMetadata": [("op_name", 2, "string", False)],
}
_messages = {}


def _message(name: str):
    if not _messages:
        from google.protobuf import descriptor_pb2, descriptor_pool, \
            message_factory

        F = descriptor_pb2.FieldDescriptorProto
        kinds = {"string": F.TYPE_STRING, "bytes": F.TYPE_BYTES,
                 "int64": F.TYPE_INT64}
        fdp = descriptor_pb2.FileDescriptorProto(
            name="bench_scopes_trace.proto", package="bench_scopes",
            syntax="proto3")
        for msg, fields in _SCHEMA.items():
            m = fdp.message_type.add(name=msg)
            for fname, num, kind, rep in fields:
                f = m.field.add(name=fname, number=num, label=(
                    F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL))
                if kind in kinds:
                    f.type = kinds[kind]
                else:
                    f.type, f.type_name = F.TYPE_MESSAGE, \
                        f".bench_scopes.{kind}"
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fdp)
        for msg in _SCHEMA:
            _messages[msg] = message_factory.GetMessageClass(
                pool.FindMessageTypeByName(f"bench_scopes.{msg}"))
    return _messages[name]


def op_names(xplane: Path) -> dict:
    """``{program: {instruction: op_name}}`` from the trace's HLO protos;
    a program is named as on the ``XLA Modules`` line."""
    space = _message("XSpace")()
    space.ParseFromString(Path(xplane).read_bytes())
    out = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        for entry in plane.event_metadata:
            for stat in entry.value.stats:
                if not stat.bytes_value:
                    continue
                hlo = _message("HloProto")()
                hlo.ParseFromString(stat.bytes_value)
                out[entry.value.name] = {
                    i.name: i.metadata.op_name
                    for c in hlo.hlo_module.computations
                    for i in c.instructions}
    return out


def load(xplane: Path) -> dict:
    from jax.profiler import ProfileData

    names = op_names(xplane)
    pd = ProfileData.from_file(str(xplane))
    devices, paths, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(T.DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
                if line.name == T.OPS_LINE:
                    ops += [(T.short(n), s, e) for n, s, e in ev]
                elif line.name == MODULES_LINE:
                    mods += ev
            if ops:
                ops.sort(key=lambda o: (o[1], -o[2]))
                devices[plane.name] = ops
                paths[plane.name] = _paths(
                    ops, sorted(mods, key=lambda m: m[1]), names)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIXES)]
    return {"devices": devices, "paths": paths, "spans": spans}


def _paths(ops, mods, names) -> list:
    """Each operation's op_name, from the program whose run holds it."""
    out, j = [], 0
    for op, s, _ in ops:
        while j < len(mods) and mods[j][2] <= s:
            j += 1
        prog = mods[j][0] if j < len(mods) and mods[j][1] <= s else None
        out.append(names.get(prog, {}).get(op, ""))
    return out


def scopes_of(path: str) -> list:
    """Scope names along an op_name path, outermost first."""
    out = []
    for part in path.split("/")[:-1]:
        for s in _SCOPE.findall(part):
            if s not in out:
                out.append(s)
    return out


def reduce(ev: dict, top: int = 10) -> dict:
    win = [s for s in ev["spans"] if s[0] == T.SPAN_PREFIX + "window"]
    if not win or not ev["devices"]:
        raise ValueError("trace has no bench.window span or no device ops")
    w0, w1 = win[0][1], win[0][2]
    n_dev = len(ev["devices"])
    scope_t, own_t, path_t = (defaultdict(float), defaultdict(float),
                              defaultdict(float))
    unscoped, first_union = 0.0, None
    for dev in sorted(ev["devices"]):
        iv = [(max(s, w0), min(e, w1), p) for (_, s, e), p in
              zip(ev["devices"][dev], ev["paths"][dev]) if e > w0 and s < w1]
        selfs = defaultdict(float)
        _self_paths(iv, selfs)
        for path, t in selfs.items():
            t /= n_dev
            names = scopes_of(path)
            if not names:
                unscoped += t
                continue
            for name in names:
                scope_t[name] += t
            own_t[names[-1]] += t
            path_t[path.rsplit("/", 1)[0]] += t
        if first_union is None:
            first_union = T._union([(s, e) for s, e, _ in iv])
    edges = [w0] + [x for s, e in first_union for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle = defaultdict(float)
    for g in gaps:
        idle[_idle_label(g, ev["spans"])] += (g[1] - g[0]) * 1e-9
    paths = sorted(path_t.items(), key=lambda kv: kv[1], reverse=True)
    return {
        "scopes": {k: v * 1e-9 for k, v in sorted(scope_t.items())},
        "scopes_own": {k: v * 1e-9 for k, v in sorted(own_t.items())},
        "unscoped": unscoped * 1e-9,
        "scope_paths": [[p, t * 1e-9] for p, t in paths[:top]],
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
    }


def _self_paths(iv, acc):
    """Self time by path; an operation without one takes the innermost
    enclosing operation's (``trace_reduce._self_times``' nesting)."""
    stack = []
    for s, e, path in sorted(iv, key=lambda x: (x[0], -x[1])):
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            stack.pop()
        if not path and stack:
            path = stack[-1][2]
        if stack:
            acc[stack[-1][2]] -= e - s
        acc[path] += e - s
        stack.append((s, e, path))


def _idle_label(gap, spans) -> str:
    mid = (gap[0] + gap[1]) / 2
    cover = [s for s in spans if s[0].startswith(PROGRAM_SPANS)
             and s[1] <= mid <= s[2]]
    if cover:
        return min(cover, key=lambda s: s[2] - s[1])[0]
    bench = [s for s in spans if s[0].startswith(T.SPAN_PREFIX)
             and s[0] != T.SPAN_PREFIX + "window"]
    return T._label(gap, bench)


def newest_xplane(trace_dir: Path):
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def read_run(rec: dict, root: Path):
    """The reduction of the trace the run of ``rec`` wrote under
    ``root/.bench_out/trace``, with ``busy_s`` and ``window_s``; ``None``
    when it wrote none there (a run without ``--trace 1``: a trace older
    than the run's window is another run's) or one without device
    operations."""
    w = rec.get("record", {}).get("window") or {}
    t0 = w.get("window_t0", w.get("t0"))
    xplane = newest_xplane(Path(root) / ".bench_out" / "trace")
    if t0 is None or xplane is None:
        return None
    # the window's start on the wall clock
    if xplane.stat().st_mtime < time.time() - (time.perf_counter() - t0):
        return None
    return _reduce_file(str(xplane), xplane.stat().st_mtime)


@functools.lru_cache(maxsize=1)  # the readers of one run share a reduction
def _reduce_file(xplane: str, mtime: float):
    ev = load(Path(xplane))
    if not ev["devices"] or not any(
            sp[0] == T.SPAN_PREFIX + "window" for sp in ev["spans"]):
        return None
    busy = T.reduce({"devices": ev["devices"], "spans": ev["spans"]})
    return dict(reduce(ev), busy_s=busy["busy_s"], window_s=busy["window_s"])


def share(red, names, own=()) -> float | None:
    """Percent of busy time under the scopes ``names``, plus the own time
    (``scopes_own``) of the scopes ``own``; no operation may be counted
    twice. ``None`` when no operation in the window lies under any of
    them: a program without named scopes, or one whose scope was renamed
    or whose work moved out of it, gives no reading rather than a share
    of 0."""
    if red is None or not red["busy_s"]:
        return None
    found = [red["scopes"][n] for n in names if n in red["scopes"]]
    found += [red["scopes_own"][n] for n in own if n in red["scopes_own"]]
    return 100.0 * sum(found) / red["busy_s"] if found else None


def main(argv=None) -> int:
    arg = Path((argv or sys.argv[1:])[0])
    xplane = arg if arg.suffix == ".pb" else newest_xplane(arg)
    print(json.dumps(reduce(load(xplane)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
