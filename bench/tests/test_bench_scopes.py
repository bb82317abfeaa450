"""Device time by named scope and idle time by host span (``bench.scopes``),
on hand-made windows and on a window recorded on a TPU v5e
(``data/trace_scoped_sample.json``: 6 ms of ``serve_prompt.qwen2_5_3b``
around the end of a decode tick and the start of an admitting one, with
the program's tracer on, cut from what ``bench.scopes.load`` read); and
the four per-layer readers built on it."""
import json
import os
import time
from collections import defaultdict
from pathlib import Path

import pytest

import bench_tiny_root as tiny
from bench import scopes as S
from bench import trace_reduce as T
from bench.common import load_reader

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def _hand_made():
    ops = [("while.1", 100, 600), ("fusion.1", 120, 200),
           ("copy.1", 200, 300), ("fusion.2", 300, 400),
           ("fusion.3", 700, 800), ("copy.2", 850, 900)]
    paths = ["jit(step)/engine.decode/while",
             "jit(step)/engine.decode/while/body/model.kv_write/dus",
             "",  # a copy XLA put inside the loop: takes the loop's path
             "jit(step)/engine.decode/while/body/dot_general",
             "jit(step)/engine.prefill/cond/branch_1_fun/model.kv_write/dus",
             ""]  # encloses nothing and is enclosed by nothing
    spans = [("bench.window", 100, 1000), ("bench.tick", 100, 650),
             ("serve.readback", 150, 660), ("serve.tick", 90, 990),
             ("host.gc", 810, 840)]
    return {"devices": {DEV: ops}, "paths": {DEV: paths}, "spans": spans}


def test_hand_made_scopes_and_inheritance():
    r = S.reduce(_hand_made())
    sc = r["scopes"]
    # while.1's self time (500 - 280) + fusion.1 + copy.1 + fusion.2
    assert sc["engine.decode"] == pytest.approx(500e-9)
    assert sc["model.kv_write"] == pytest.approx(180e-9)
    assert sc["engine.prefill"] == pytest.approx(100e-9)
    assert r["unscoped"] == pytest.approx(50e-9)
    busy = T.reduce({"devices": {DEV: _hand_made()["devices"][DEV]},
                     "spans": _hand_made()["spans"]})["busy_s"]
    # the outermost scopes plus unscoped are the busy time
    assert sc["engine.decode"] + sc["engine.prefill"] + r["unscoped"] \
        == pytest.approx(busy)
    paths = dict(r["scope_paths"])
    assert paths["jit(step)/engine.decode"] == pytest.approx(320e-9)


def test_hand_made_own_time_of_the_layer_scan():
    """``scopes_own`` gives each operation to its innermost scope: the
    layer scan's slicing, stacking and loop copies are ``model.layers``'
    own time, its body's work is not."""
    pre = "jit(step)/engine.decode/while/body/model.layers"
    ops = [("while.2", 0, 1000), ("ds_fusion", 100, 200),
           ("dot_fusion", 200, 500), ("dus_fusion.1", 500, 600),
           ("copy.3", 600, 700), ("dus_fusion.2", 700, 800)]
    paths = [pre + "/while",
             pre + "/while/body/dynamic_slice",
             pre + "/while/body/closed_call/model.block/dot_general",
             pre + "/while/body/closed_call/model.block/model.kv_write/dus",
             "",  # inside the layer loop: takes the loop's path
             pre + "/while/body/dynamic_update_slice"]
    r = S.reduce({"devices": {DEV: ops}, "paths": {DEV: paths},
                  "spans": [("bench.window", 0, 1000)]})
    # while.2's self time 300, the slice, the copy and the stacking
    assert r["scopes_own"] == {"model.layers": pytest.approx(600e-9),
                               "model.block": pytest.approx(300e-9),
                               "model.kv_write": pytest.approx(100e-9)}
    assert r["scopes"]["model.layers"] == pytest.approx(1000e-9)
    assert r["scopes"]["engine.decode"] == pytest.approx(1000e-9)
    assert "engine.decode" not in r["scopes_own"]
    red = dict(r, busy_s=1000e-9)
    assert S.share(red, ("model.kv_write",), own=("model.layers",)) \
        == pytest.approx(70.0)


def test_share_reads_the_scopes_present():
    # XLA may fuse one scope's work into another's operations (AdamW's
    # moments into the apply): the scopes found still give the share
    red = {"scopes": {"optim.apply": 0.5}, "scopes_own": {}, "busy_s": 2.0}
    assert S.share(red, ("optim.clip", "optim.adamw", "optim.apply")) \
        == pytest.approx(25.0)
    assert S.share(red, ("optim.clip",)) is None
    assert S.share(dict(red, busy_s=0.0), ("optim.apply",)) is None


def test_hand_made_idle_by_span():
    r = S.reduce(_hand_made())
    # gaps 600-700 (middle 650: serve.readback is the innermost program
    # span), 800-850 (host.gc), 900-1000 (serve.tick)
    assert r["idle_by_span"] == {
        "serve.readback": pytest.approx(100e-9),
        "serve.tick": pytest.approx(100e-9),
        "host.gc": pytest.approx(50e-9)}


def test_idle_without_program_spans_takes_the_bench_label():
    ev = _hand_made()
    ev["spans"] = [s for s in ev["spans"] if s[0].startswith("bench.")]
    r = S.reduce(ev)
    # 600-700's middle lies on the tick's end
    assert r["idle_by_span"] == {"in bench.tick": pytest.approx(100e-9),
                                 "after bench.tick": pytest.approx(150e-9)}


def test_two_devices_average():
    ev = {"devices": {DEV: [("a", 0, 50)], "/device:TPU:1": [("a", 0, 100)]},
          "paths": {DEV: ["jit(f)/optim.adamw/mul"],
                    "/device:TPU:1": ["jit(f)/optim.adamw/mul"]},
          "spans": [("bench.window", 0, 100)]}
    r = S.reduce(ev)
    assert r["scopes"] == {"optim.adamw": pytest.approx(75e-9)}
    # device 0's idle only
    assert sum(r["idle_by_span"].values()) == pytest.approx(50e-9)


@pytest.mark.parametrize("path,want", [
    ("jit(step)/transpose(jvp(pipeline.head))/dot_general",
     ["pipeline.head"]),
    ("jit(step)/pipeline.bwd/cond/jit(step)/pipeline.bwd/pipeline.accum/add",
     ["pipeline.bwd", "pipeline.accum"]),
    ("state.active", []),  # an argument's name is no scope
    ("jit(step)/while/body/closed_call/add", []),
    ("", []),
])
def test_scopes_of(path, want):
    assert S.scopes_of(path) == want


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        S.reduce({"devices": {DEV: [("a", 0, 1)]}, "paths": {DEV: [""]},
                  "spans": []})


def test_old_sample_unchanged():
    """The recorded window of the first benchmark still reduces as it did:
    ``bench.scopes`` adds to ``trace_reduce`` and changes none of it."""
    ev = json.loads((DATA / "trace_sample.json").read_text())
    r = T.reduce(ev)
    assert r["busy_s"] == pytest.approx(0.002105408)
    assert r["window_s"] == pytest.approx(0.00596761)
    assert r["device_ops"][:3] == [["copy.170", pytest.approx(5.7508e-4)],
                                   ["copy.169", pytest.approx(5.74138e-4)],
                                   ["fusion.323", pytest.approx(3.70425e-4)]]
    assert r["idle_gaps"][0] == ["after bench.tick",
                                 pytest.approx(0.003861493)]
    assert len(r["idle_gaps"]) == 10
    # with no paths every operation is unscoped
    s = S.reduce(dict(ev, paths={d: [""] * len(o)
                                 for d, o in ev["devices"].items()}))
    assert s["scopes"] == {} and s["scope_paths"] == []
    assert s["unscoped"] == pytest.approx(r["busy_s"])


@pytest.fixture(scope="module")
def sample():
    return json.loads((DATA / "trace_scoped_sample.json").read_text())


def test_recorded_outermost_scopes_and_unscoped_are_busy(sample):
    r = S.reduce(sample)
    busy = T.reduce({"devices": sample["devices"],
                     "spans": sample["spans"]})["busy_s"]
    w0, w1 = sample["spans"][0][1:]
    outer = 0.0  # each operation once, whatever its scopes
    for dev in sample["devices"]:
        iv = [(max(s, w0), min(e, w1), p) for (_, s, e), p in
              zip(sample["devices"][dev], sample["paths"][dev])
              if e > w0 and s < w1]
        acc = defaultdict(float)
        S._self_paths(iv, acc)
        outer += sum(t for p, t in acc.items() if S.scopes_of(p))
    assert outer * 1e-9 + r["unscoped"] == pytest.approx(busy, rel=1e-9)
    assert r["unscoped"] <= 0.05 * busy
    for name in ("engine.decode", "engine.prefill", "engine.admit"):
        assert r["scopes"].get(name, 0) > 0, name


def test_recorded_inheritance(sample):
    """Operations with no ``op_name`` of their own that lie inside a
    scoped one are counted under its scopes."""
    ops, paths = sample["devices"][DEV], sample["paths"][DEV]
    own = S.reduce(dict(sample, paths={DEV: [
        p if p else "jit(x)/unscoped_here/op" for p in paths]}))
    inherited = S.reduce(sample)
    assert any(not p for p in paths)
    assert inherited["unscoped"] < own["unscoped"]
    assert sum(inherited["scopes"].values()) > sum(own["scopes"].values())
    assert len(ops) == len(paths)


def test_recorded_idle_by_span_sums_to_idle(sample):
    r = S.reduce(sample)
    t = T.reduce({"devices": sample["devices"], "spans": sample["spans"]})
    idle = sum(r["idle_by_span"].values())
    assert idle == pytest.approx(t["window_s"] - t["busy_s"], rel=1e-9)
    named = sum(v for k, v in r["idle_by_span"].items()
                if k.startswith(S.PROGRAM_SPANS))
    assert named >= 0.9 * idle


# -- the readers -------------------------------------------------------------
RED = {"scopes": {"engine.prefill": 0.5, "engine.decode": 1.2,
                  "model.layers": 1.1, "model.block": 0.7,
                  "model.kv_write": 0.3, "optim.clip": 0.01,
                  "optim.adamw": 0.1, "optim.apply": 0.09,
                  "pipeline.accum": 0.2, "pipeline.bwd": 0.9},
       "scopes_own": {"engine.prefill": 0.2, "engine.decode": 0.2,
                      "model.layers": 0.4, "model.block": 0.4,
                      "model.kv_write": 0.3, "optim.clip": 0.01,
                      "optim.adamw": 0.1, "optim.apply": 0.09,
                      "pipeline.accum": 0.2, "pipeline.bwd": 0.7},
       "unscoped": 0.0, "busy_s": 2.0, "window_s": 2.1}


def _renamed(red):
    """The same reduction with every scope under another name."""
    return dict(red, **{k: {"x" + n: v for n, v in red[k].items()}
                        for k in ("scopes", "scopes_own")})


@pytest.mark.parametrize("name,want", [
    ("prefill_share.serve", 25.0), ("kv_write_share.serve", 35.0),
    ("optimizer_share.train", 10.0), ("accum_share.train", 10.0)])
def test_readers(monkeypatch, name, want):
    read = load_reader(name, tiny.REPO)
    monkeypatch.setattr(S, "read_run", lambda rec, root: RED)
    assert read({}) == pytest.approx(want)
    # a program without named scopes, or a run without a trace: nothing
    monkeypatch.setattr(S, "read_run", lambda rec, root: dict(
        RED, scopes={}, scopes_own={}))
    assert read({}) is None
    # a scope renamed, or its work moved out of it: nothing, not 0%
    monkeypatch.setattr(S, "read_run", lambda rec, root: _renamed(RED))
    assert read({}) is None
    monkeypatch.setattr(S, "read_run", lambda rec, root: None)
    assert read({}) is None


def test_read_run_finds_only_this_runs_trace(tmp_path):
    rec = {"record": {"window": {"window_t0": time.perf_counter()}}}
    assert S.read_run(rec, tmp_path) is None  # no trace at all
    old = tmp_path / ".bench_out/trace/cell/plugins/profile/1/h.xplane.pb"
    old.parent.mkdir(parents=True)
    old.write_bytes(b"")
    past = time.time() - 3600
    os.utime(old, (past, past))  # written before this window began
    assert S.read_run(rec, tmp_path) is None
    assert S.read_run({"record": {}}, tmp_path) is None
