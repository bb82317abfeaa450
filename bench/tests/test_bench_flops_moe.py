"""The expert share's operation and byte counts against a hand count, and
the readers of ``mfu.train_split`` and ``roofline.moe_ffn.train`` on
recorded numbers."""
import json
from pathlib import Path

import pytest

import bench_tiny_root  # noqa: F401  (puts the repository on the path)
from bench import flops, flops_moe
from bench.common import load_reader

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "bench/configs/qwen3_moe_30b_a3b.json").read_text())
DENSE = json.loads((ROOT / "bench/configs/qwen2_5_3b.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# one held choice a token a layer: 8 choices over 128 experts, 16 held
ROWS = 4 * 32768


def test_train_flops_hand_count():
    per_layer = (2048 * 4096          # Wq
                 + 2 * 2048 * 512     # Wk, Wv (4 KV heads of 128)
                 + 4096 * 2048        # Wo
                 + 2048 * 128)        # router over all 128 experts
    assert flops_moe.dense_layer_params(CFG) == per_layer == 19_136_512
    assert flops_moe.expert_row_params(CFG) == 3 * 2048 * 768
    fwd = (2 * 4 * per_layer * 32768                  # projections, router
           + 4 * 32 * 128 * 4 * 32768 * 8193 / 2      # attention, causal
           + 2 * 2048 * 18992 * 32768                 # head over the slice
           + 2 * 3 * 2048 * 768 * ROWS)               # held experts' rows
    got = flops_moe.train_flops(CFG, 4, 32768, 8192, ROWS)
    assert got == pytest.approx(3 * fwd, rel=1e-12)
    # about 1.6 GFLOP a token, 53 TFLOP a step of 32,768 tokens
    assert got / 32768 == pytest.approx(1.61e9, rel=0.01)


def test_ffn_flops_and_bytes_hand_count():
    assert flops_moe.ffn_flops(CFG, ROWS) == 18 * 2048 * 768 * ROWS
    weights = 16 * 3 * 2048 * 768 * 2        # 16 held experts, bf16
    rows = ROWS * (2048 + 2048) * 2          # each row in and out, bf16
    assert flops_moe.ffn_bytes(CFG, 16, ROWS) == 3 * (16 * weights + rows)


def _rec(cfg, batch, layers, chips, moe_rows):
    return {"cfg": cfg, "peaks": PEAKS, "record": {
        "window": {"steps": 20, "t0": 1.0, "t1": 21.0}, "batch": batch,
        "layers": layers, "chips": chips, "moe_rows": moe_rows,
        "steps": [{"t0": 0.0, "t1": 1.0}] * 20}}


def test_mfu_train_split_counts_held_rows_and_chips():
    read = load_reader("mfu.train_split")
    batch = {"rows": 4, "seq": 8192, "microbatches": 4}
    counts = [{"step": i, "held": [ROWS // 4] * 4, "largest": [3000] * 4}
              for i in range(3)]
    want = flops_moe.train_flops(CFG, 4, 32768, 8192, ROWS) / 197e12 * 100
    assert read(_rec(CFG, batch, 4, 1, counts)) == pytest.approx(want)
    assert read(_rec(CFG, batch, 4, 1, [])) is None  # no counter, no reading
    split = {"rows": 8, "seq": 2048, "microbatches": 8}
    want = (16384 * flops.train_flops_per_token(DENSE, 8, 2048)
            / (4 * 197e12) * 100)
    assert read(_rec(DENSE, split, 8, 4, [])) == pytest.approx(want)


def test_roofline_moe_ffn_reads_nothing_without_a_trace():
    read = load_reader("roofline.moe_ffn.train")
    batch = {"rows": 4, "seq": 8192, "microbatches": 4}
    counts = [{"step": 0, "held": [ROWS // 4] * 4, "largest": [3000] * 4}]
    rec = _rec(CFG, batch, 4, 1, counts)
    rec["record"]["window"]["t0"] = None
    assert read(rec) is None
