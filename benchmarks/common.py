"""Shared benchmark utilities: run configs, timing, CSV emission."""
from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

OUT_DIR = os.environ.get("BENCH_OUT", "experiments/bench")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO_ROOT, "BENCH_throughput.json")


def record_baseline(entries: dict, *, force: bool = False,
                    path: str | None = None) -> list:
    """Merge NEW metric keys into a write-once baseline JSON.

    ``path`` defaults to ``BENCH_throughput.json`` (resolved at call
    time so tests can monkeypatch ``BASELINE_PATH``); the serving
    benchmark records into ``BENCH_serving.json`` with the same
    write-once/--force semantics.

    Existing keys are REFUSED, not clobbered: re-recording a key that is
    already in the baseline requires ``force=True`` (the benchmark CLIs'
    ``--force``) or ``BENCH_THROUGHPUT_REFRESH=1``, and only the CALLER'S
    keys are ever rewritten - other benchmarks' entries are always
    preserved. A newly added metric is backfilled the first time it is
    measured. Callers skip this entirely in smoke mode. Returns the list
    of keys actually written.
    """
    if path is None:
        path = BASELINE_PATH
    refresh = force or os.environ.get("BENCH_THROUGHPUT_REFRESH") == "1"
    if os.path.exists(path):
        with open(path) as f:
            baseline = json.load(f)
    else:
        baseline = {}
    missing = [k for k in entries if refresh or k not in baseline]
    refused = [k for k in entries if k not in missing]
    if refused:
        print(
            f"record_baseline: write-once, refusing to overwrite {refused} "
            f"in {os.path.basename(path)} (pass --force / force=True or set "
            "BENCH_THROUGHPUT_REFRESH=1 to re-record)",
            file=sys.stderr, flush=True,
        )
    if not missing:
        return []
    for k in missing:
        baseline[k] = entries[k]
    with open(path, "w") as f:
        json.dump(baseline, f, indent=1, default=float)
    return missing


@dataclass(frozen=True)
class BenchConfig:
    quick: bool = True
    # CI smoke mode (benchmarks/run.py --smoke): tiny episode/step counts so
    # the figure scripts execute end-to-end in minutes on CPU, and NO
    # baseline JSON writes (the numbers are meaningless for tracking).
    smoke: bool = False
    # vmapped env population per training chunk (rollout engine). 1 keeps
    # the seed's episode ordering and updates-per-env-step ratio (updates
    # are batched at chunk end either way - see train_sac's docstring);
    # raise it, e.g. BenchConfig(num_envs=8), to trade per-episode update
    # freshness for wall-clock. Metrics stay per-episode regardless.
    num_envs: int = 1
    # shard the population axis of SAC training over this many host devices
    # (None = no mesh, plain vmap). Threaded into train_sac/train_population
    # by train_standard_agents and the fig benchmarks.
    shard_devices: Optional[int] = None
    # stop/resume knobs threaded into the SAC trainers: each trained agent
    # checkpoints under {checkpoint_dir}/{algo} every checkpoint_every
    # episodes and resumes from an existing checkpoint by default.
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    # which LeakageModel the leakage figures price hops with: "analytic"
    # (the paper's closed-form Eq. 30 values, the default) or "empirical"
    # (per-layer values measured by training the FSHA-style attacker
    # population of repro.attack - see leakage_model()).
    leakage: str = "analytic"

    def leakage_model(self, seed: int = 0):
        """None for the analytic default (MHSLEnv's built-in
        AnalyticLeakage), or a trained EmpiricalLeakage - making the
        learned attacker a one-flag swap for every fig benchmark."""
        if self.leakage == "analytic":
            return None
        if self.leakage != "empirical":
            raise ValueError(f"unknown leakage model {self.leakage!r}")
        from repro.attack import train_empirical_model

        return train_empirical_model(seed=seed,
                                     steps=120 if self.smoke else 400)

    @property
    def episodes(self) -> int:
        if self.smoke:
            return 8
        return 160 if self.quick else 400

    @property
    def warmup(self) -> int:
        if self.smoke:
            return 2
        return 15 if self.quick else 30

    @property
    def eval_episodes(self) -> int:
        if self.smoke:
            return 4
        return 15 if self.quick else 50

    def mesh(self):
        """Population mesh for the configured device count (None = no mesh)."""
        if self.shard_devices is None:
            return None
        from repro.distribution.mesh import make_population_mesh

        return make_population_mesh(self.shard_devices)

    def ckpt(self, name: str):
        """Per-agent checkpoint subdirectory (None when checkpointing off)."""
        if self.checkpoint_dir is None:
            return None
        return os.path.join(self.checkpoint_dir, name)


def derived_seed(seed: int, idx: int) -> int:
    """Per-variant seed for multi-variant benchmarks: distinct streams so
    ablation deltas aren't correlated-noise artifacts, deterministic in the
    base seed so default runs stay reproducible. idx 0 keeps ``seed``."""
    return seed + 7919 * idx  # 7919: prime stride, no overlap for idx < stride


def train_standard_agents(env, bench: BenchConfig, seed: int = 0, *,
                          episodes: int | None = None,
                          warmup: int | None = None,
                          algos=("icm_ca", "sac", "ppo"),
                          scenario=None, num_envs: int | None = None,
                          ckpt_ns: str | None = None):
    """The agent-training preamble shared by fig4/fig5/fig6.

    Trains the requested algorithms on ``env`` (optionally under a
    ``ScenarioParams`` override) and returns
    ``{name: {"params", "cfg", "result", "seconds"}}``. Algorithms:
    ``icm_ca`` (full SAC), ``sac`` (no ICM/CA ablation), ``ppo``, ``dqn``.

    ``ckpt_ns`` namespaces this call's checkpoints under
    ``bench.checkpoint_dir`` (e.g. ``"fig4"``): different figures train
    agents with the same names, so checkpointing is OFF unless the caller
    provides a namespace - resuming another figure's agent would silently
    return its curves.
    """
    from repro.core.agents.dqn import DQNConfig, train_dqn
    from repro.core.agents.loops import train_sac
    from repro.core.agents.ppo import PPOConfig, train_ppo
    from repro.core.agents.sac import SACConfig

    episodes = bench.episodes if episodes is None else episodes
    warmup = bench.warmup if warmup is None else warmup
    num_envs = bench.num_envs if num_envs is None else num_envs
    # mesh + resume knobs ride on the SAC trainer (the engine's mesh-aware
    # path); PPO/DQN have no population/mesh trainer yet
    mesh = bench.mesh()

    def ck(name):
        return bench.ckpt(f"{ckpt_ns}/{name}") if ckpt_ns else None

    out = {}
    for name in algos:
        with Timer() as t:
            if name == "icm_ca":
                cfg = SACConfig()
                res = train_sac(env, cfg, episodes=episodes,
                                warmup_episodes=warmup, seed=seed,
                                num_envs=num_envs, scenario=scenario,
                                mesh=mesh, checkpoint_dir=ck(name),
                                checkpoint_every=bench.checkpoint_every)
            elif name == "sac":
                cfg = SACConfig(use_icm=False, use_ca=False)
                res = train_sac(env, cfg, episodes=episodes,
                                warmup_episodes=warmup, seed=seed,
                                num_envs=num_envs, scenario=scenario,
                                mesh=mesh, checkpoint_dir=ck(name),
                                checkpoint_every=bench.checkpoint_every)
            elif name == "ppo":
                cfg = PPOConfig()
                res = train_ppo(env, cfg, episodes=episodes, seed=seed,
                                num_envs=num_envs, scenario=scenario)
            elif name == "dqn":
                cfg = DQNConfig(eps_decay_episodes=max(episodes // 2, 1))
                res = train_dqn(env, cfg, episodes=episodes, seed=seed,
                                num_envs=num_envs, scenario=scenario)
            else:
                raise ValueError(f"unknown algo {name!r}")
        out[name] = {"params": res.params, "cfg": cfg, "result": res,
                     "seconds": t.seconds}
    return out


def save_json(name: str, payload) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def emit_csv_row(name: str, us_per_call: float, derived: str) -> None:
    """Scaffold contract: ``name,us_per_call,derived``."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def smooth(xs, k: int = 10):
    xs = np.asarray(xs, dtype=np.float64)
    if len(xs) < k:
        return xs
    kern = np.ones(k) / k
    return np.convolve(xs, kern, mode="valid")


def episodes_to_reach(rewards, threshold: float) -> int:
    """First episode whose smoothed reward crosses `threshold` (paper's
    convergence-rate metric); len(rewards) if never."""
    sm = smooth(rewards)
    idx = np.argmax(sm >= threshold)
    if sm[idx] < threshold:
        return len(rewards)
    return int(idx)


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.seconds = time.time() - self.t0
