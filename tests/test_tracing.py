"""The program's tracer (``repro.tracing``): null default, nesting, dump,
garbage-collection spans."""
import gc
import json

import jax
import pytest

from repro.tracing import NULL_TRACER, Tracer


def test_null_tracer_records_nothing_and_builds_no_annotation(monkeypatch):
    built = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: built.append(a))
    ctx = NULL_TRACER.span("serve.tick", tick=1)
    assert NULL_TRACER.span("serve.admit") is ctx  # one shared context
    with ctx as attrs:
        assert attrs is None
        NULL_TRACER.event("serve.request", 0.0, 1.0, rid=1)
    assert NULL_TRACER.events() == [] and built == []
    assert not NULL_TRACER.enabled


def test_nesting_parents_and_attrs():
    with Tracer() as tr:
        with tr.span("serve.tick", tick=0) as a:
            with tr.span("serve.admit"):
                pass
            with tr.span("serve.dispatch"):
                pass
            a["active_after"] = 2
    ev = {e["name"]: e for e in tr.events()}
    tick, admit = ev["serve.tick"], ev["serve.admit"]
    disp = ev["serve.dispatch"]
    assert tick["parent"] is None
    assert admit["parent"] == tick["id"] and disp["parent"] == tick["id"]
    assert len({tick["id"], admit["id"], disp["id"]}) == 3
    assert tick["tick"] == 0 and tick["active_after"] == 2
    assert tick["t0"] <= admit["t0"] <= admit["t1"] <= disp["t0"] \
        <= disp["t1"] <= tick["t1"]
    # spans are recorded as they close: children before their parent
    names = [e["name"] for e in tr.events()]
    assert names.index("serve.admit") < names.index("serve.tick")


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


def test_spans_and_gc_are_profiler_annotations(monkeypatch):
    anns = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", anns)
    with Tracer() as tr:
        with tr.span("serve.tick"):
            with tr.span("serve.readback"):
                pass
        gc.collect()
    assert anns.log[:4] == [("enter", "serve.tick"),
                            ("enter", "serve.readback"),
                            ("exit", "serve.readback"),
                            ("exit", "serve.tick")]
    assert ("enter", "host.gc") in anns.log[4:]
    assert anns.log.count(("enter", "host.gc")) == \
        anns.log.count(("exit", "host.gc"))


def test_span_closes_on_error():
    with Tracer() as tr:
        with pytest.raises(RuntimeError):
            with tr.span("serve.fault"):
                raise RuntimeError("down")
        with tr.span("serve.tick"):
            pass
    ev = tr.events()
    assert [e["name"] for e in ev] == ["serve.fault", "serve.tick"]
    assert ev[1]["parent"] is None


def test_dump_round_trips(tmp_path):
    import numpy as np

    with Tracer() as tr:
        with tr.span("serve.admit", pending=np.float32(1.5)):
            pass
        with tr.span("serve.tick", packed=[np.int64(3), 4],
                     free=np.int32(2)):
            pass
    path = tmp_path / "trace.jsonl"
    tr.dump(path)
    back = [json.loads(line) for line in path.read_text().splitlines()]
    assert back == json.loads(json.dumps(tr.events(), default=lambda x:
                                         x.tolist()))
    assert back[1]["packed"] == [3, 4] and back[1]["free"] == 2


def test_gc_spans_around_a_forced_collection():
    with Tracer() as tr:
        with tr.span("serve.drain"):
            gc.collect()
    gcs = [e for e in tr.events() if e["name"] == "host.gc"]
    drain = [e for e in tr.events() if e["name"] == "serve.drain"][0]
    assert gcs and gcs[-1]["generation"] == 2
    assert all(e["parent"] == drain["id"] for e in gcs)
    assert drain["t0"] <= gcs[-1]["t0"] <= gcs[-1]["t1"] <= drain["t1"]
    # a closed tracer hears nothing more
    n = len(tr.events())
    gc.collect()
    assert len(tr.events()) == n


def test_compile_spans():
    with Tracer() as tr:
        jax.jit(lambda x: x * 3 + 1).lower(1.0).compile()
    kinds = {e.get("kind") for e in tr.events() if e["name"] == "jax.compile"}
    assert "compile" in kinds
    for e in tr.events():
        if e["name"] == "jax.compile":
            assert 0 <= e["t1"] - e["t0"] < 60


def test_counter_readings_nest_and_dump(tmp_path):
    with Tracer() as tr:
        with tr.span("bench.wait", index=3):
            tr.count("moe.rows", [812, 790], step=3, largest=[70, 66])
        tr.count("moe.rows", [800, 801], step=4, largest=[64, 69])
    NULL_TRACER.count("moe.rows", [1], step=0)
    ev = [e for e in tr.events() if e["name"] == "moe.rows"]
    wait = next(e for e in tr.events() if e["name"] == "bench.wait")
    assert [e["value"] for e in ev] == [[812, 790], [800, 801]]
    assert ev[0]["parent"] == wait["id"] and ev[1]["parent"] is None
    assert ev[0]["largest"] == [70, 66] and ev[0]["t"] <= ev[1]["t"]
    path = tmp_path / "trace.jsonl"
    tr.dump(path)
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert [x["step"] for x in lines if x["name"] == "moe.rows"] == [3, 4]
    assert NULL_TRACER.events() == []
