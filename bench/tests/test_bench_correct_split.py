"""``correct`` for the split training cells (``bench.drive_train_split``),
at a CPU size through ``run_cell``: the program passes; a step that
returns its state unchanged and one that leaves out half the batch do
not; the float8 control does not. The expert-share cell runs here on one
device. The benchmark has no four-chip cell yet (its limits need the
chips' readings at its size); the four-stage path of
``bench.drive_train_split`` runs here as a tiny cell added to the copy,
in a child process with four host devices."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import bench_tiny_root as tiny
from bench import faults
from bench.drive_train_split import moe_model_config, token_batches

MOE = "train_moe8k.qwen3_moe_30b_a3b"
SPLIT = "train_split4.tiny_qwen2_5_3b"
# 1F1B over an 8-layer cut split (2,3,6,8), one stage a host device
SPLIT_MIX = {"path": "train_split", "ahead_s": 4.0,
             "boundaries": [2, 3, 6, 8], "tokens": {"kind": "uniform"},
             "batch": {"rows": 8, "seq": 32, "microbatches": 8, "pool": 8},
             "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                           "weight_decay": 0.0, "max_grad_norm": 1.0}}
# The tiny expert share: 2 of 4 experts held (experts 2-3), top 2.
TINY_MOE = {"router_experts": 4, "num_experts": 2, "expert_start": 2,
            "num_experts_per_tok": 2, "moe_intermediate_size": 128}
# Readings of the tiny expert share (CPU, seeds 2**31 + 9, 5 and 11):
# program loss 1.2e-4-3.4e-4, gradient 1.1e-3-2.5e-3, change 5.1e-4-5.8e-4;
# float8 control 4.9e-4-1.0e-3, 1.7e-2-5.5e-2, 1.7e-3-5.0e-3; half batch
# 5.7e-4-9.7e-3, 0.18-0.19, 8.9e-2-0.11; state unchanged 5.9e-5-3.6e-4,
# 1, 1. Routing flips between bfloat16 and float32 router inputs make the
# share noisier than the dense cell.
MOE_LIMITS = {"loss_gap": 9e-4, "grad_gap": 1e-2, "delta_gap": 0.05}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make(tmp_path_factory.mktemp("split"))
    path = root / "bench/configs/tiny_qwen3_moe_30b_a3b.json"
    cfg = json.loads(path.read_text())
    cfg.update(TINY_MOE)
    path.write_text(json.dumps(cfg))
    (root / f"bench/limits/{MOE}.json").write_text(json.dumps(MOE_LIMITS))
    return root


def test_moe_program_correct(root):
    line, checks = tiny.run(MOE, 2**31 + 9, 1.0, False, root)
    assert line["correct"], checks
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_moe_fault_is_not_correct(root, fault):
    line, checks = tiny.run(MOE, 11, 0.5, False, root,
                            hooks={"train_step": faults.TRAIN[fault]})
    assert not line["correct"], checks


def test_moe_control_through_harness_is_not_correct(root):
    line, checks = tiny.run(MOE, 2**31 + 9, 0.5, False, root,
                            hooks={"control": True})
    assert not line["correct"], checks


def test_moe_config_refuses_a_program_without_qk_norm_or_share(
        root, monkeypatch):
    """A program whose ModelConfig has no QK-norm or no expert share (the
    parent's) is refused at once, never run as another model."""
    from repro.configs import get_config

    cfg = json.loads(
        (root / "bench/configs/tiny_qwen3_moe_30b_a3b.json").read_text())
    name = f"repro.configs.{cfg['module']}"
    __import__(name)
    mod = sys.modules[name]
    assert moe_model_config(cfg, 4).moe.held == 2
    without_share = types.SimpleNamespace(moe=types.SimpleNamespace())
    monkeypatch.setattr(mod, "CONFIG", without_share)
    with pytest.raises(SystemExit, match="expert share"):
        moe_model_config(cfg, 4)
    from dataclasses import replace

    monkeypatch.setattr(mod, "CONFIG", replace(
        get_config("qwen3-moe-30b-a3b").reduced(), qk_norm=False))
    with pytest.raises(SystemExit, match="qk_norm"):
        moe_model_config(cfg, 4)


def test_zipf_tokens_are_seeded_and_next_token_labelled():
    mix = {"tokens": {"kind": "zipf", "s": 1.1},
           "batch": {"pool": 2, "rows": 3, "seq": 4096}}
    tok, lab = token_batches(mix, 2**31 + 1, 512)
    again, _ = token_batches(mix, 2**31 + 1, 512)
    assert tok.shape == lab.shape == (2, 3, 4096)
    assert np.array_equal(tok, again)
    assert np.array_equal(tok[..., 1:], lab[..., :-1])
    # rank 1 takes 1 / H(512, 1.1) = 19.1% of the ids, rank 2 8.9%
    share = np.bincount(tok.ravel(), minlength=512) / tok.size
    assert 0.175 < share[0] < 0.21 and 0.08 < share[1] < 0.10
    assert share[0] > share[1] > share[10] > share[500]


SPLIT_RUNS = """
import json, sys
sys.path[:0] = [{repo!r}, {src!r}, {tests!r}]
import bench_tiny_root as tiny
from pathlib import Path
from bench import faults
root = tiny.make(Path({tmp!r}))
bench = json.loads((root / "BENCHMARK.json").read_text())
bench["workloads"].append({{"name": {cell!r}, "config": "tiny_qwen2_5_3b",
                           "traffic": "train_split4", "chips": 4}})
for m in bench["end_to_end"]:
    if m["name"] == "train_tokens_per_s":
        m["workloads"].append({cell!r})
(root / "BENCHMARK.json").write_text(json.dumps(bench))
(root / "bench/traffic/train_split4.json").write_text(json.dumps({mix!r}))
(root / "bench/limits/{cell}.json").write_text(json.dumps(
    {{k: tiny.LIMITS[k] for k in ("loss_gap", "grad_gap", "delta_gap")}}))
for name, hooks in (("program", None),
                    ("half_batch", {{"train_step": faults.TRAIN["half_batch"]}}),
                    ("control", {{"control": True}})):
    line, checks = tiny.run({cell!r}, 2**31 + 9, 0.5, False, root,
                            hooks=hooks)
    print("RUN", json.dumps({{"name": name, "correct": line["correct"],
                             "count": line["device"]["count"],
                             "checks": checks}}), flush=True)
"""


@pytest.fixture(scope="module")
def split_runs(tmp_path_factory):
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    code = SPLIT_RUNS.format(repo=repo, src=os.path.join(repo, "src"),
                             tests=here, cell=SPLIT, mix=SPLIT_MIX,
                             tmp=str(tmp_path_factory.mktemp("split4")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    runs = [json.loads(x[4:]) for x in out.stdout.splitlines()
            if x.startswith("RUN ")]
    return {r["name"]: r for r in runs}


def test_split4_program_correct_on_four_stages(split_runs):
    run = split_runs["program"]
    assert run["correct"] and run["count"] == 4, run


@pytest.mark.parametrize("name", ["half_batch", "control"])
def test_split4_fault_and_control_are_not_correct(split_runs, name):
    assert not split_runs[name]["correct"], split_runs[name]
