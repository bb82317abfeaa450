"""Host spans of the program; off unless a ``Tracer`` is passed.

    tracer = Tracer()                  # or NULL_TRACER, the default
    with tracer.span("serve.tick", tick=3) as attrs:
        ...
        attrs["active_after"] = 5       # attrs may be filled in late
    tracer.count("moe.rows", [812, 790], step=7)   # a counter reading
    tracer.dump("trace.jsonl")          # one JSON object per line
    tracer.close()

A counter reading is one event ``{name, t, value, id, parent, **attrs}``
(``t`` on ``time.perf_counter``, ``value`` a number or a list of them).
A span is one event ``{name, t0, t1, id, parent, **attrs}`` on
``time.perf_counter``, recorded when it closes. Each span also enters a
``jax.profiler.TraceAnnotation`` of its name, which puts it on the
profiler's host planes beside the device's operations when a profiler
trace is being taken (and costs next to nothing when none is).

Until ``close`` it also records ``host.gc`` spans (from
``gc.callbacks``, annotated the same way) and ``jax.compile`` spans (from
``jax.monitoring``'s durations of backend compiles, ``kind="compile"``,
and persistent-cache loads, ``kind="cache_load"``; these are reported
once finished, so they carry no annotation).

``NULL_TRACER`` records nothing: its ``span`` returns one shared
``contextlib.nullcontext`` whose value is ``None``, and it reads no clock.
A caller that builds attributes at a cost checks ``tracer.enabled`` first.
"""
from __future__ import annotations

import contextlib
import gc
import itertools
import json
import time
from pathlib import Path

# durations from jax.monitoring recorded as jax.compile spans
COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class Tracer:
    enabled = True

    def __init__(self):
        import jax.monitoring

        self._events: list[dict] = []
        self._stack: list[int] = []
        self._ids = itertools.count(1)
        self._gc_open = None  # (t0, annotation) of a collection under way
        self._open = True
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = _annotation(name)
        sid, parent = next(self._ids), self._parent()
        self._stack.append(sid)
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            t1 = time.perf_counter()
            self._stack.pop()
            ann.__exit__(None, None, None)
            self._events.append(dict(attrs, name=name, t0=t0, t1=t1, id=sid,
                                     parent=parent))

    def event(self, name: str, t0: float, t1: float, **attrs) -> None:
        """A span timed elsewhere (``t0``/``t1`` on ``perf_counter``), as
        a child of the span open now."""
        self._events.append(dict(attrs, name=name, t0=t0, t1=t1,
                                 id=next(self._ids), parent=self._parent()))

    def count(self, name: str, value, **attrs) -> None:
        """A counter reading, as a child of the span open now."""
        self._events.append(dict(attrs, name=name, t=time.perf_counter(),
                                 value=value, id=next(self._ids),
                                 parent=self._parent()))

    def events(self) -> list[dict]:
        return list(self._events)

    def dump(self, path) -> None:
        with open(Path(path), "w") as f:
            for e in self._events:
                f.write(json.dumps(e, default=_plain) + "\n")

    def close(self) -> None:
        """Stop recording ``host.gc`` and ``jax.compile`` spans."""
        if self._open:
            import jax.monitoring

            self._open = False
            gc.callbacks.remove(self._on_gc)
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _parent(self):
        return self._stack[-1] if self._stack else None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_open = (time.perf_counter(), _annotation("host.gc"))
        elif self._gc_open is not None:
            t0, ann = self._gc_open
            self._gc_open = None
            ann.__exit__(None, None, None)
            self.event("host.gc", t0, time.perf_counter(),
                       generation=info["generation"],
                       collected=info["collected"])

    def _on_duration(self, event, duration, **kw):
        kind = COMPILE_EVENTS.get(event)
        if kind is not None:
            t1 = time.perf_counter()
            self.event("jax.compile", t1 - duration, t1, kind=kind,
                       fun=kw.get("fun_name"))


class _NullTracer:
    enabled = False
    _ctx = contextlib.nullcontext()

    def span(self, name, **attrs):
        return self._ctx

    def event(self, name, t0, t1, **attrs) -> None:
        pass

    def count(self, name, value, **attrs) -> None:
        pass

    def events(self) -> list:
        return []

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


NULL_TRACER = _NullTracer()


def _annotation(name: str):
    import jax

    ann = jax.profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


def _plain(x):
    """numpy scalars and arrays in attributes, for ``json``."""
    return x.tolist() if hasattr(x, "tolist") else str(x)
