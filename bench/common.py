"""Shared pieces of the harness: files found by name, host spans, device
facts and the program's configuration check.

Layout under ``bench/`` (a later cell, mix or metric adds files only):

* ``configs/<config>.json``  sizes as run, source, cuts, assumptions;
* ``traffic/<mix>.json``     parameters of one mix and the path it drives
  (``serve`` or ``train``: ``drive_<path>.py``);
* ``limits/<cell>.json``     each compared number's limit for one cell;
* ``metrics/<name>.py``      ``read(rec)`` of one per-layer metric;
* ``peaks.json``             chip peaks keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload named {name!r} in BENCHMARK.json")


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, the cell's entry, its configuration, its mix)."""
    bench = load_json(root / "BENCHMARK.json")
    cell = find_cell(bench, name)
    cfg = load_json(root / "bench" / "configs" / f"{cell['config']}.json")
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, cfg, mix


def metrics_of(bench: dict, cell: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if cell in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in names)]
    return e2e, layer


def load_reader(name: str, root: Path = ROOT):
    """``bench/metrics/<name>.py``'s ``read`` function."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Spans:
    """Host spans on ``perf_counter``; with ``annotate`` each also goes into
    the profiler's trace as a ``TraceAnnotation`` of the same name."""

    def __init__(self, annotate: bool):
        self.events: list[dict] = []
        self.annotate = annotate

    @contextmanager
    def span(self, name: str, **attrs):
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield attrs
        attrs.update(name=name, t0=t0, t1=time.perf_counter())
        self.events.append(attrs)

    def of(self, name: str) -> list[dict]:
        return [e for e in self.events if e["name"] == name]


def check_program_config(cfg: dict, mc, layers: int) -> None:
    """The program's ModelConfig has to be the configuration the file
    states; a mismatch is an error, never a silent other model."""
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "vocab_size": cfg["vocab_size"],
            "num_layers": layers, "rope_theta": cfg["rope_theta"],
            "norm_eps": cfg["rms_norm_eps"],
            "qkv_bias": cfg["attention_bias"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "activation": "swiglu", "arch_type": "dense"}
    got = {k: getattr(mc, k) for k in want}
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad or mc.attention_window is not None:
        raise SystemExit(f"bench: program config differs from "
                         f"{cfg['name']}.json (program, file): {bad}")


def program_model_config(cfg: dict, layers: int):
    import dataclasses
    import importlib as il

    mod = il.import_module(f"repro.configs.{cfg['module']}")
    mc = dataclasses.replace(mod.CONFIG, num_layers=layers)
    check_program_config(cfg, mc, layers)
    return mc


def program_bytes(compiled) -> int:
    """Bytes one compiled program holds on its device: arguments, outputs
    not aliased to them, and temporaries."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def peak_in_use(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def quantile(xs, q: float) -> float:
    """Linear-interpolation quantile (numpy's default)."""
    import numpy as np

    return float(np.percentile(np.asarray(xs, float), 100 * q))


def eprint(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
