"""``correct`` for the training cell, at a CPU size: the program passes;
a step that returns its state unchanged and one that leaves out half the
batch do not; the float8 control fails at least one number."""
import pytest

import bench_tiny_root as tiny
from bench import faults
from bench.common import Spans, load_json
from bench.drive_train import TrainCell, compare

CELL = "train_1f1b.qwen2_5_3b"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("train"))


def test_program_correct(root):
    line, checks = tiny.run(CELL, 2**31 + 9, 1.0, False, root)
    assert line["correct"], checks
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(root, fault):
    line, checks = tiny.run(CELL, 11, 0.5, False, root,
                            hooks={"train_step": faults.TRAIN[fault]})
    assert not line["correct"], checks


def test_control_fails_a_number(root):
    cfg = load_json(root / "bench/configs/tiny_qwen2_5_3b.json")
    mix = load_json(root / "bench/traffic/train_1f1b.json")
    tc = TrainCell(cfg, mix, 4, Spans(annotate=False))
    tc.free_program_state()
    ref = tc.reference("f32")
    nums = compare(tc.reference("fp8"), ref)
    assert any(nums[k] > tiny.LIMITS[k] for k in nums), nums


def test_control_through_harness_is_not_correct(root):
    line, checks = tiny.run(CELL, 2**31 + 17, 0.5, False, root,
                            hooks={"control": True})
    assert not line["correct"], checks
