"""The training window at a CPU size: every step sent ahead is waited for
and counted, and a traced run reports the training cell's per-layer
metrics."""
import bench_tiny_root as tiny
from bench.common import Spans, load_json
from bench.drive_train import CHECKED_STEPS, TrainCell

CELL = "train_1f1b.qwen2_5_3b"


def test_every_sent_step_counts(tmp_path):
    root = tiny.make(tmp_path)
    cfg = load_json(root / "bench/configs/tiny_qwen2_5_3b.json")
    mix = load_json(root / "bench/traffic/train_1f1b.json")
    tc = TrainCell(cfg, mix, 5, Spans(annotate=False))
    tc.first_steps()
    w = tc.window(0.5)
    assert w["depth"] >= 1
    assert w["steps"] == tc.i - CHECKED_STEPS == len(w["intervals"])
    assert w["tokens"] == w["steps"] * mix["batch"]["rows"] * 32
    ends = [s["t1"] for s in w["intervals"]]
    assert ends == sorted(ends) and w["t0"] < ends[0] <= ends[-1] <= w["t1"]
    assert len(tc.spans.of("bench.wait")) == w["steps"]


def test_traced_run_reports_layer_metrics(tmp_path):
    root = tiny.make(tmp_path)
    line, checks = tiny.run(CELL, 2**31 + 23, 1.0, True, root)
    assert line["correct"], checks
    assert set(line["metrics"]) == {"step_ms.train", "mfu.train",
                                    "idle_share.train"}
    assert line["metrics"]["step_ms.train"]["value"] > 0
