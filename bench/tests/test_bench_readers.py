"""The per-layer metric readers on a hand-made record of three ticks."""
import json

import pytest

import bench_tiny_root as tiny
from bench import flops
from bench.common import load_reader

CFG = json.loads((tiny.REPO / "bench/configs/qwen2_5_3b.json").read_text())
PEAKS = json.loads((tiny.REPO / "bench/peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def _rec(trace=None):
    ticks = [
        # admits request 0 (prompt 100): prefill + 8 decode steps
        {"t0": 0.0, "t1": 0.5, "admitted": [0], "done": [],
         "slots": [(0, 1, 8)], "active_after": 1},
        # decodes it 8 more steps
        {"t0": 0.51, "t1": 0.61, "admitted": [], "done": [],
         "slots": [(0, 9, 8)], "active_after": 1},
        # 3 steps, then it is done; the loop idles after this tick
        {"t0": 0.63, "t1": 0.70, "admitted": [], "done": [0],
         "slots": [(0, 17, 3)], "active_after": 0},
    ]
    return {"record": {"ticks": ticks, "window": {"plen": {0: 100}}},
            "cfg": CFG, "peaks": PEAKS, "trace": trace}


def read(name, rec):
    return load_reader(name, tiny.REPO)(rec)


def test_tick_medians():
    rec = _rec()
    assert read("admit_tick_ms", rec) == pytest.approx(500.0)
    assert read("decode_tick_ms", rec) == pytest.approx(85.0)
    # gaps while a slot is active: 0.5->0.51 and 0.61->0.63
    assert read("host_gap_ms.serve", rec) == pytest.approx(15.0)


def test_mfu_prefill_and_decode():
    rec = _rec()
    need = flops.prefill_flops(CFG, 36, 100)
    assert read("mfu.prefill", rec) == pytest.approx(
        100 * need / 0.5 / PEAKS["bf16_flops_per_s"])
    # one slot: the weights dominate, so the bytes bound binds
    wb = flops.weight_bytes(CFG, 36, 2)
    kv = flops.kv_bytes_per_token(CFG, 36, 2)
    least = ((8 * wb + kv * sum(100 + i for i in range(9, 17)))
             + (3 * wb + kv * sum(100 + i for i in range(17, 20)))) \
        / PEAKS["hbm_bytes_per_s"]
    assert read("mfu.decode", rec) == pytest.approx(100 * least / 0.17)
    assert 0 < read("mfu.decode", rec) <= 100


def test_no_source_no_number():
    rec = _rec()
    assert read("idle_share.serve", rec) is None
    rec["record"]["ticks"] = []
    for name in ("admit_tick_ms", "decode_tick_ms", "host_gap_ms.serve",
                 "mfu.prefill", "mfu.decode"):
        assert read(name, rec) is None
    assert read("idle_share.serve", _rec({"busy_s": 3.0,
                                          "window_s": 4.0})) == 25.0


def test_token_gaps_per_served_token():
    from bench.drive_serve import token_gaps

    ticks = _rec()["record"]["ticks"]
    gaps, count = token_gaps(ticks)
    # 8 tokens with the first one, 8 over 0.11 s, 3 over 0.09 s
    assert count == {0: 19}
    assert gaps == pytest.approx([0.0] * 8 + [0.11 / 8] * 8 + [0.03] * 3)
    # their mean is the request's (last tick end - first) / (tokens - 1)
    assert sum(gaps) / len(gaps) == pytest.approx((0.70 - 0.5) / 19)
