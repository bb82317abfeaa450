"""The trace reduction, on a hand-made window and on a short window
recorded on a TPU v5e (``data/trace_sample.json``: the end of one engine
tick of ``serve_prompt.qwen2_5_3b`` and the start of the next, 6 ms cut
from a profiler trace read by ``trace_reduce.load``)."""
import json
from pathlib import Path

import pytest

import bench_tiny_root  # noqa: F401
from bench import trace_reduce as T

SAMPLE = Path(__file__).resolve().parent / "data" / "trace_sample.json"


def test_hand_made_window():
    ev = {"devices": {"/device:TPU:0": [
        ("fusion.1", 100, 300), ("fusion.2", 250, 400),  # overlap: 100-400
        ("dot.3", 600, 700), ("fusion.1", 900, 1000),
        ("copy", 0, 150),  # starts before the window
    ]}, "spans": [("bench.window", 100, 1100), ("bench.tick", 120, 720),
                  ("bench.tick", 880, 1050)]}
    r = T.reduce(ev)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(500e-9)  # 100-400, 600-700, 900-1000
    # self time: the clipped copy (100-150) lies inside fusion.1 (100-300);
    # fusion.2 overlaps fusion.1 without lying inside it
    assert r["device_ops"] == [["fusion.1", pytest.approx(250e-9)],
                               ["fusion.2", pytest.approx(150e-9)],
                               ["dot.3", pytest.approx(100e-9)],
                               ["copy", pytest.approx(50e-9)]]
    gaps = {tuple(g) for g in r["idle_gaps"]}
    assert ("in bench.tick", pytest.approx(200e-9)) in [
        (a, b) for a, b in gaps]  # 400-600, inside the first tick
    assert any(a == "after bench.tick" and b == pytest.approx(200e-9)
               for a, b in gaps)  # 700-900: its middle lies between ticks
    assert sum(b for _, b in r["idle_gaps"]) == pytest.approx(500e-9)


def test_two_devices_average():
    ev = {"devices": {"/device:TPU:0": [("a", 0, 50)],
                      "/device:TPU:1": [("a", 0, 100)]},
          "spans": [("bench.window", 0, 100)]}
    r = T.reduce(ev)
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["device_ops"] == [["a", pytest.approx(75e-9)]]


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        T.reduce({"devices": {"/device:TPU:0": [("a", 0, 1)]}, "spans": []})


def test_recorded_chip_window():
    ev = json.loads(SAMPLE.read_text())
    r = T.reduce(ev)
    assert 0 < r["busy_s"] <= r["window_s"]
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= 10
    assert 0 < sum(times) <= r["busy_s"] * (1 + 1e-9)
    for label, t in r["idle_gaps"]:
        assert label.split(" ", 1)[0] in ("in", "after", "before")
        assert 0 < t <= r["window_s"] - r["busy_s"] + 1e-12
