"""``correct`` for the serving cells, at a CPU size: the program passes,
the float8 control and a token altered where the engine produces it do
not, each through the harness's whole run. Limits and readings are in
``bench_tiny_root``."""
import pytest

import bench_tiny_root as tiny
from bench import faults
from bench.common import Spans, load_json
from bench.drive_serve import ServeCell

CELLS = ["serve_prompt.qwen2_5_3b", "serve_decode.stablelm_1_6b"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("serve"))


@pytest.mark.parametrize("cell", CELLS)
def test_program_correct(root, cell):
    line, checks = tiny.run(cell, 2**31 + 5, 2.0, False, root)
    assert line["correct"], checks
    assert line["failed"] == 0 and line["attempted"] == 8
    assert list(line)[-1] == "checks"
    want = {"setup_s", "tpot_p95_ms"} | (
        {"ttft_p95_ms"} if cell.startswith("serve_prompt") else set())
    assert set(line["metrics"]) == want


@pytest.mark.parametrize("cell", CELLS)
def test_token_altered_is_not_correct(root, cell):
    bench = load_json(root / "BENCHMARK.json")
    cfg_name = [w["config"] for w in bench["workloads"]
                if w["name"] == cell][0]
    vocab = load_json(root / "bench/configs" / f"{cfg_name}.json")[
        "vocab_size"]
    line, checks = tiny.run(
        cell, 7, 2.0, False, root,
        hooks={"engine_step": faults.token_altered(vocab)})
    assert not line["correct"]
    assert checks[0][1] > 10 * tiny.LIMITS["served_gap"]


@pytest.mark.parametrize("cell,traffic,config", [
    ("serve_prompt.qwen2_5_3b", "serve_prompt", "tiny_qwen2_5_3b"),
    ("serve_decode.stablelm_1_6b", "serve_decode", "tiny_stablelm_1_6b")])
def test_control_is_not_correct(root, cell, traffic, config):
    cfg = load_json(root / "bench/configs" / f"{config}.json")
    mix = load_json(root / "bench/traffic" / f"{traffic}.json")
    sc = ServeCell(cfg, mix, 3, Spans(annotate=False))
    sc.warm()
    sc.window(2.0)
    sc.free_program_state()
    chk = sc.check(control=True)
    assert chk["served_gap"] <= tiny.LIMITS["served_gap"]
    assert chk["control_gap"] > tiny.LIMITS["served_gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_through_harness_is_not_correct(root, cell):
    line, checks = tiny.run(cell, 2**31 + 13, 2.0, False, root,
                            hooks={"control": True})
    assert not line["correct"]
    assert checks[0][1] > tiny.LIMITS["served_gap"]
