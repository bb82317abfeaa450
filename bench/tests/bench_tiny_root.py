"""A temporary copy of the benchmark at a CPU-sized scale, for the tests.

The copy holds ``BENCHMARK.json`` and ``bench/`` with every cell pointed
at a ``tiny_<config>.json``: 2 layers, d_model 256, run by a program
configuration module registered here (``repro.configs.tiny_<module>``,
the ``.reduced()`` preset of the same module), short prompts and answers,
and limits set for this size. Its peaks table has an entry for the CPU so
that the harness runs; no number of such a run is a device number.
``run`` drives ``bench.run.run_cell`` with the look for a chip and the
reduction of the device trace left out, since the CPU has neither.
"""
from __future__ import annotations

import importlib
import json
import shutil
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"hidden_size": 256, "intermediate_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "num_hidden_layers": 2, "vocab_size": 512, "train_layers": 2}

# Readings at this size on the CPU, seeds 1-4: the program's served gap
# 0-0.008, the float8 control's 0.088-0.155, a token altered 1.37-1.50;
# training, program / control: loss 7.1e-5-7.6e-5 / 2.9e-4-7.1e-4, first
# gradient 3.9e-4-8.4e-4 / 6.7e-3-1.7e-2, change 3.9e-3-6.0e-3 / (no
# separation; a state left unchanged reads 1).
LIMITS = {"served_gap": 0.03, "loss_gap": 1.5e-4, "grad_gap": 3e-3,
          "delta_gap": 0.05}


def _edit(path: Path, fn) -> None:
    d = json.loads(path.read_text())
    fn(d)
    path.write_text(json.dumps(d, indent=1))


def _tiny_module(module: str) -> str:
    """Register ``repro.configs.tiny_<module>``, whose ``CONFIG`` is the
    module's ``.reduced()`` preset; returns its short name."""
    name = f"tiny_{module}"
    full = f"repro.configs.{name}"
    if full not in sys.modules:
        real = importlib.import_module(f"repro.configs.{module}")
        mod = types.ModuleType(full)
        mod.CONFIG = real.CONFIG.reduced()
        sys.modules[full] = mod
    return name


def make(tmp: Path) -> Path:
    root = Path(tmp) / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        w["config"] = "tiny_" + w["config"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    for c in {w["config"][5:] for w in bench["workloads"]}:
        cfg = json.loads((REPO / "bench/configs" / f"{c}.json").read_text())
        cfg.update(TINY, name="tiny_" + c, module=_tiny_module(cfg["module"]))
        (root / "bench/configs" / f"tiny_{c}.json").write_text(
            json.dumps(cfg, indent=1))

    def peaks(d):
        d["devices"]["cpu"] = d["devices"]["TPU v5 lite"]

    _edit(root / "bench/peaks.json", peaks)
    for mix in (root / "bench/traffic").glob("*.json"):
        def small(d):
            if d["path"] == "serve":
                d["engine"].update(prompt_pad=64, max_new=32)
                d["prompt_len"].update(median=16, max=64)
                d["output_len"].update(median=8, max=32)
                d["arrivals"]["rate_per_s"] = 4.0
                d["check"]["tokens"] = 64
            else:
                d["batch"].update(seq=32)

        _edit(mix, small)
    for lim in (root / "bench/limits").glob("*.json"):
        _edit(lim, lambda d: d.update({k: LIMITS[k] for k in d}))
    return root


# what the reduction of a device trace gives, for a run with no device
NO_TRACE = {"busy_s": 1.0, "window_s": 1.0, "device_ops": [],
            "idle_gaps": []}


def run(cell: str, seed: int, seconds: float, trace: bool, root: Path,
        hooks=None):
    """``bench.run.run_cell`` on the CPU. The compile cache is pointed into
    the copy, so that the repository's own cache is never written."""
    from bench import run as brun
    from bench import trace_reduce
    from repro.launch.compile_cache import ENV_VAR

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(ENV_VAR, str(root / ".jax_cache"))
        mp.setattr(brun, "check_device", lambda chips: None)
        mp.setattr(trace_reduce, "reduce_dir", lambda d: NO_TRACE)
        return brun.run_cell(cell, seed, seconds, trace, root=root,
                             hooks=hooks)
