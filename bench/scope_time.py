"""Device time under one named scope given by its full name, such as
``model.moe.ffn``: ``bench.scopes`` names a scope by its first two dotted
parts alone, so that scope's operations count under ``model.moe`` there.

``under(rec, root, name)`` reads the trace a ``--trace 1`` run has just
written (``bench.scopes.read_run``'s rule) and gives the self time, in
seconds and averaged over devices, of the operations in the
``bench.window`` span whose op_name has a path component that is
``name``, bare or wrapped (``transpose(jvp(model.moe.ffn))``); ``None``
where the run wrote no trace.

XLA:TPU rewrites ``lax.ragged_dot`` into grouped-matmul kernels whose
op_name is their own (``ragged-dot-none``, ``ragged-dot-metadata``), not
the scope they were traced in. In this program only the expert layer's
grouped matmul makes them, so they count under ``model.moe.ffn``
(``RENAMED``).
"""
from __future__ import annotations

import functools
import re
from collections import defaultdict
from pathlib import Path

from bench import scopes
from bench import trace_reduce as T

RENAMED = {"ragged-dot": "model.moe/model.moe.ffn"}


def under(rec: dict, root: Path, name: str) -> float | None:
    if scopes.read_run(rec, root) is None:
        return None
    xplane = scopes.newest_xplane(Path(root) / ".bench_out" / "trace")
    pat = re.compile(rf"(^|\(){re.escape(name)}(\)|$)")
    return sum(t for p, t in _self_by_path(str(xplane),
                                          xplane.stat().st_mtime).items()
               if any(pat.search(c) for c in p.split("/")[:-1]))


@functools.lru_cache(maxsize=1)
def _self_by_path(xplane: str, mtime: float) -> dict:
    """op_name -> self time (s) in the window, averaged over devices."""
    ev = scopes.load(Path(xplane))
    w0, w1 = next((s, e) for n, s, e in ev["spans"]
                  if n == T.SPAN_PREFIX + "window")
    out = defaultdict(float)
    for dev in ev["devices"]:
        scopes._self_paths(
            [(max(s, w0), min(e, w1), _scoped(p)) for (_, s, e), p in
             zip(ev["devices"][dev], ev["paths"][dev]) if e > w0 and s < w1],
            out)
    return {p: t * 1e-9 / len(ev["devices"]) for p, t in out.items()}


def _scoped(path: str) -> str:
    for prefix, scope in RENAMED.items():
        if path.startswith(prefix):
            return f"{scope}/{path}"
    return path
