"""Post-SPMD HLO analysis: collective counts and bytes.

The compiled module text is PER-DEVICE (SPMD partitioned), so shapes parsed
here are per-device shard shapes.

Wire-byte factors per collective (ring algorithms, n = replica group size):
  all-reduce        2 (n-1)/n * result_bytes
  all-gather          (n-1)/n * result_bytes   (result = gathered)
  reduce-scatter      (n-1)   * result_bytes   (result = one shard)
  all-to-all          (n-1)/n * result_bytes
  collective-permute          result_bytes
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2  # conservative default


# ---------------------------------------------------------------------------
# while-loop aware computation parsing
# ---------------------------------------------------------------------------

_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_WHILE_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_TRIP_RE = re.compile(r"known_trip_count\D+(\d+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def split_computations(hlo_text: str):
    """Split module text into {computation_name: body_text}.

    Computation headers start at column 0 (optionally 'ENTRY') and end
    with '{'; bodies are indented; the closing '}' is at column 0.
    """
    comps = {}
    cur_name, cur_lines = None, []
    for line in hlo_text.splitlines():
        stripped = line.rstrip()
        if cur_name is None:
            if stripped.endswith("{") and stripped and not line[0].isspace():
                m = _COMP_HEAD_RE.match(stripped)
                if m:
                    cur_name = m.group(1)
                    cur_lines = []
        else:
            if stripped == "}":
                comps[cur_name] = "\n".join(cur_lines)
                cur_name = None
            else:
                cur_lines.append(line)
    return comps


def loop_multipliers(hlo_text: str):
    """Effective execution-count multiplier per computation.

    Each `while` op's body executes trip-count times (parsed as the largest
    integer constant in its condition computation - the canonical
    lax.scan lowering compares the induction variable against the length).
    Multipliers compose through nesting via the computation call graph.
    """
    comps = split_computations(hlo_text)
    # find while ops: (enclosing_comp, body_name, trip_count). The CPU/TPU
    # pipelines record known_trip_count in backend_config; fall back to the
    # largest constant in the condition computation.
    whiles = []
    for name, body in comps.items():
        for line in body.splitlines():
            if " while(" not in line:
                continue
            mb = _WHILE_BODY_RE.search(line)
            if not mb:
                continue
            mt = _TRIP_RE.search(line)
            if mt:
                tc = int(mt.group(1))
            else:
                mc = _WHILE_COND_RE.search(line)
                consts = [int(c) for c in _CONST_RE.findall(comps.get(mc.group(1), ""))] if mc else []
                tc = max(consts) if consts else 1
            whiles.append((name, mb.group(1), tc))

    # called-computations edges (calls, fusions, while bodies, conditionals)
    single_re = re.compile(r"(?:to_apply|body|condition)=%?([\w.\-]+)")
    braced_re = re.compile(r"(?:calls|branch_computations)=\{([^}]*)\}")
    children = {name: set() for name in comps}
    for name, body in comps.items():
        for m in single_re.finditer(body):
            children[name].add(m.group(1))
        for m in braced_re.finditer(body):
            for c in re.split(r",\s*", m.group(1)):
                children[name].add(c.strip().lstrip("%"))

    while_body_trip = {}
    for _, body_name, tc in whiles:
        while_body_trip[body_name] = max(while_body_trip.get(body_name, 1), tc)

    # propagate multipliers from the entry computation
    mult = {}

    def visit(name, m, depth=0):
        if depth > 50 or name not in comps:
            return
        if mult.get(name, 0) >= m:
            return
        mult[name] = m
        for c in children.get(name, ()):  # body computations multiply by trip
            cm = m * while_body_trip.get(c, 1)
            visit(c, cm, depth + 1)

    # entry = computation not called by anyone
    called = set()
    for cs in children.values():
        called |= cs
    entries = [n for n in comps if n not in called]
    for e in entries:
        visit(e, 1)
    return comps, mult


@dataclass
class CollectiveStats:
    # per-device result bytes and wire-byte estimates, per collective kind
    result_bytes: Dict[str, int] = field(default_factory=dict)
    wire_bytes: Dict[str, float] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)


def parse_collectives(hlo_text: str, *, loop_aware: bool = False) -> CollectiveStats:
    """Sum collective bytes in the module.

    loop_aware=True multiplies collectives inside while-loop bodies by the
    loop trip count (lax.scan over layers) so a scan-mode compile yields
    the same totals as a fully unrolled one.
    """
    if loop_aware:
        comps, mult = loop_multipliers(hlo_text)
        stats = CollectiveStats()
        for name, body in comps.items():
            m = mult.get(name, 1)
            sub = _parse_flat(body)
            for kind in sub.result_bytes:
                stats.result_bytes[kind] = (
                    stats.result_bytes.get(kind, 0) + sub.result_bytes[kind] * m
                )
                stats.wire_bytes[kind] = (
                    stats.wire_bytes.get(kind, 0.0) + sub.wire_bytes[kind] * m
                )
                stats.counts[kind] = stats.counts.get(kind, 0) + sub.counts[kind] * m
        return stats
    return _parse_flat(hlo_text)


def _parse_flat(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        for kind in _COLLECTIVES:
            # match " <result-type> kind(" to avoid matching metadata/calls
            marker = f" {kind}("
            marker_start = f" {kind}-start("
            if marker not in line and marker_start not in line:
                continue
            lhs = line.split(f"{kind}-start(" if marker_start in line else f"{kind}(")[0]
            # result type(s) appear between '=' and the op name
            try:
                result_part = lhs.split("=", 1)[1]
            except IndexError:
                continue
            rb = _shape_bytes(result_part)
            if rb == 0:
                continue
            n = _group_size(line)
            if kind == "all-reduce":
                wb = 2 * (n - 1) / n * rb
            elif kind == "all-gather":
                wb = (n - 1) / n * rb
            elif kind == "reduce-scatter":
                wb = (n - 1) * rb
            elif kind == "all-to-all":
                wb = (n - 1) / n * rb
            else:  # collective-permute
                wb = float(rb)
            stats.result_bytes[kind] = stats.result_bytes.get(kind, 0) + rb
            stats.wire_bytes[kind] = stats.wire_bytes.get(kind, 0.0) + wb
            stats.counts[kind] = stats.counts.get(kind, 0) + 1
            break
    return stats


def pipeline_collective_counts(
    hlo_text: str, n_ticks: int = 1, *, loop_aware: bool = True
) -> Dict[str, float]:
    """Issued-collective counts per pipeline tick, by collective kind.

    The 1F1B executor issues its stage hops (``collective-permute``,
    possibly split into async ``-start``/``-done`` pairs - only the start
    is counted) and its loss/grad reductions (``all-reduce``) inside the
    tick scan; loop-aware parsing multiplies body ops by the scan trip
    count, and dividing by ``n_ticks`` normalizes to per-tick issue
    counts. This is the regression surface for the double-buffered
    transport: overlap moves the hops to the top of the tick but must not
    issue MORE of them than the synchronous handoff.
    """
    stats = parse_collectives(hlo_text, loop_aware=loop_aware)
    return {k: c / n_ticks for k, c in stats.counts.items()}
