"""RL engine throughput: legacy per-step loop vs device-resident engine.

Measures, with the SAME ``SACConfig`` on the current backend:

* ``env_steps_per_sec`` - the seed's per-step host loop (one jit dispatch
  per env call, host history window) vs the vmapped ``lax.scan`` rollout.
* ``updates_per_sec`` - per-call jitted SAC updates fed by the host-numpy
  replay buffer vs the fused update scan sampling the device buffer (both
  sides run the seed's sequential three-backward update, so this metric
  keeps tracking pure dispatch overhead).
* ``update_path`` - the gradient-update ladder on the device buffer:
  seed host loop -> fused scan (sequential update) -> fused scan with the
  single-backward JOINT update (``cfg.joint_update``, shared
  critic/ICM forwards). CI gates joint-fused >= 1x the seed loop; on the
  2-core CPU box it lands ~1.35x (small-op dispatch bound - the 128-wide
  layers leave little backward-count FLOP savings to reclaim), with the
  structural headroom aimed at accelerator backends.
* ``fused_chunk`` - end-to-end training-chunk rate: the PR-3 loop
  (three dispatches per chunk, ``int(buf.size)`` host sync, full-obs
  transfer + per-row Python state hashing) vs ONE buffer-donated
  ``make_train_chunk`` call with device-reduced metrics, plus the
  resulting ``train_sac`` episodes/sec.
* ``scenario_sweep`` - a 5-point ``monitor_prob`` evaluation sweep: the
  seed's per-point loop (fresh env + fresh jits per point, one recompile
  each) vs one stacked-``ScenarioParams`` call through the population
  evaluator (compiles exactly once). Acceptance: >=3x wall-clock.
* ``sharded_population`` - the mesh-sharded population path: a
  scenarios x envs rollout on a multi-device population mesh
  (``XLA_FLAGS=--xla_force_host_platform_device_count=4`` in a clean
  subprocess, so the measurement is independent of the parent's device
  count) vs the same population on one device. Records env-steps/sec for
  both; on forced CPU host devices the "speedup" only tracks XLA's
  thread partitioning, so it is reported, not gated.

Emits the scaffold CSV rows, saves each run's numbers to the bench OUT_DIR,
and records the baseline in ``BENCH_throughput.json`` at the repo root so
later PRs can track the performance trajectory. The baseline is
write-once - an existing file is never clobbered by routine benchmark runs
(set ``BENCH_THROUGHPUT_REFRESH=1`` to re-baseline deliberately), but a
newly added metric is backfilled the first time it is measured. Smoke runs
(``--smoke``) never touch the baseline.
Acceptance for the engine PR: >=5x env-steps/sec.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dataclasses import replace

from benchmarks.common import BenchConfig, emit_csv_row, save_json
from repro.core.agents import rollout as R
from repro.core.agents import sac as SAC
from repro.core.agents.buffer import ReplayBuffer
from repro.core.agents.loops import _SAC_FIELDS, _sac_example
from repro.core.env import MHSLEnv
from repro.core.profiles import resnet101_profile
from repro.core.scenario import (
    make_population_evaluator, scenario_grid, stack_scenarios,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO_ROOT, "BENCH_throughput.json")

NUM_ENVS = 32  # engine population for the rollout measurement


def _time_legacy_rollout(env, params, cfg, episodes: int, key) -> float:
    """Seed dispatch pattern: per-step jitted calls. Returns steps/sec."""
    legacy = R.make_legacy_episode(env, R.sac_policy(env.action_dims, cfg),
                                   cfg.hist_len)
    st0 = env.reset(jax.random.PRNGKey(0))
    legacy(params, st0, key)  # warm the per-op jit caches
    t0 = time.perf_counter()
    for ep in range(episodes):
        key, k = jax.random.split(key)
        states, rewards = legacy(params, st0, k)
    jax.block_until_ready(rewards[-1])
    dt = time.perf_counter() - t0
    return episodes * env.episode_len / dt


def _time_engine_rollout(env, params, cfg, chunks: int, key) -> float:
    """Vmapped scan rollout over NUM_ENVS envs. Returns steps/sec."""
    rollout = R.make_batched_rollout(env, R.sac_policy(env.action_dims, cfg),
                                     cfg.hist_len)
    st0 = R.make_batched_reset(env)(
        jnp.broadcast_to(jax.random.PRNGKey(0), (NUM_ENVS, 2))
    )
    akeys = jax.random.split(key, NUM_ENVS)
    jax.block_until_ready(rollout(params, st0, akeys))  # compile
    t0 = time.perf_counter()
    for _ in range(chunks):
        _, traj = rollout(params, st0, akeys)
    jax.block_until_ready(traj["reward"])
    dt = time.perf_counter() - t0
    return chunks * NUM_ENVS * env.episode_len / dt


def _fill_buffers(env, params, cfg):
    """One uniform-policy chunk fills parallel host/device buffers."""
    adims = env.action_dims
    rollout = R.make_batched_rollout(env, R.uniform_policy(adims), cfg.hist_len)
    n = 64
    st0 = R.make_batched_reset(env)(
        jnp.broadcast_to(jax.random.PRNGKey(0), (n, 2))
    )
    _, traj = rollout(params, st0, jax.random.split(jax.random.PRNGKey(1), n))
    flat = R.flatten_transitions(traj, _SAC_FIELDS)

    dev_buf = R.buffer_init(cfg.buffer_size, _sac_example(env, cfg))
    dev_buf = R.buffer_add(dev_buf, flat)

    host = jax.device_get(flat)
    np_buf = ReplayBuffer(cfg.buffer_size,
                          jax.tree.map(lambda x: x[0], host))
    rows = n * env.episode_len
    for i in range(rows):
        np_buf.add(jax.tree.map(lambda x: x[i], host))
    return np_buf, dev_buf


def _time_legacy_updates(update, params, opt_state, np_buf, cfg,
                         n_updates: int) -> float:
    rng = np.random.default_rng(0)
    batch = np_buf.sample(rng, cfg.batch)
    params, opt_state, m = update(params, opt_state, batch)  # compile
    jax.block_until_ready(m)
    t0 = time.perf_counter()
    for _ in range(n_updates):
        batch = np_buf.sample(rng, cfg.batch)
        params, opt_state, m = update(params, opt_state, batch)
    jax.block_until_ready(m)
    return n_updates / (time.perf_counter() - t0)


def _time_engine_updates(update, params, opt_state, dev_buf, cfg,
                         n_updates: int, repeats: int = 4) -> float:
    fused = R.make_fused_update(update, cfg.batch, n_updates)
    key = jax.random.PRNGKey(0)
    out = fused(params, opt_state, dev_buf, key)  # compile
    jax.block_until_ready(out[2])
    t0 = time.perf_counter()
    for i in range(repeats):
        p, o, m = fused(params, opt_state, dev_buf,
                        jax.random.fold_in(key, i))
    jax.block_until_ready(m)
    return repeats * n_updates / (time.perf_counter() - t0)


def _time_update_paths(env, params, np_buf, dev_buf, cfg, n_updates: int):
    """The update ladder: seed host loop -> fused sequential -> fused joint.

    All three run the same ``SACConfig`` losses on identical buffers; the
    only variables are dispatch granularity and backward count. The two
    rungs feeding the CI gate (legacy, fused joint) take the best of two
    timing windows so a scheduling blip on a shared runner cannot flip
    the gated ratio on its own."""
    dims = env.action_dims
    seq_cfg = replace(cfg, joint_update=False)
    upd_seq, init_seq = SAC.make_update(dims, seq_cfg)
    upd_joint, init_joint = SAC.make_update(dims, replace(cfg,
                                                          joint_update=True))
    legacy = max(
        _time_legacy_updates(upd_seq, params, init_seq(params), np_buf, cfg,
                             n_updates)
        for _ in range(2)
    )
    fused_seq = _time_engine_updates(upd_seq, params, init_seq(params),
                                     dev_buf, cfg, n_updates)
    fused_joint = max(
        _time_engine_updates(upd_joint, params, init_joint(params), dev_buf,
                             cfg, n_updates)
        for _ in range(2)
    )
    return {
        "n_updates": n_updates,
        "updates_per_sec": {"legacy": legacy, "fused_sequential": fused_seq,
                            "fused_joint": fused_joint},
        "joint_speedup_vs_legacy": fused_joint / legacy,
        "joint_speedup_vs_fused_sequential": fused_joint / fused_seq,
    }


def _legacy_obs_hash(obs, bins: float = 4.0) -> int:
    """The PR-3 per-row Python state hash (kept here as the baseline's
    metric cost; the engine now packs keys on device)."""
    o = np.asarray(obs)
    discrete = o[3:]
    head = np.round(o[:3] * bins)
    return hash(tuple(np.round(discrete * bins).astype(np.int64).tolist())
                + tuple(head.astype(np.int64).tolist()))


def _time_chunk_loops(env, cfg, chunks: int, num_envs: int, key):
    """PR-3 chunk loop vs the fused train chunk, same chunk schedule.

    Both sides are warmed (compiles excluded), then timed over ``chunks``
    training chunks of ``num_envs`` episodes including all their per-chunk
    host work. Also reports end-to-end ``train_sac`` episodes/sec for the
    same workload (one-time compiles INCLUDED, as a user pays them)."""
    from repro.core.agents.loops import (
        TrainResult, _reduced_chunk_metrics, _sac_example, _SAC_FIELDS,
        train_sac,
    )

    adims = env.action_dims
    key, k0, kr, ka, ku = jax.random.split(key, 5)
    params0 = SAC.init_agent(k0, env.obs_dim, adims, cfg)
    n_updates = cfg.updates_per_step * env.episode_len * num_envs
    rkeys = jax.random.split(kr, num_envs)
    akeys = jax.random.split(ka, num_envs)
    episodes = chunks * num_envs

    # --- PR-3 replica: separate dispatches + host syncs per chunk --------
    upd_seq, init_seq = SAC.make_update(adims, replace(cfg,
                                                       joint_update=False))
    reset_batch = R.make_batched_reset(env)
    rollout_actor = R.make_batched_rollout(env, R.sac_policy(adims, cfg),
                                           cfg.hist_len)
    fused = R.make_fused_update(upd_seq, cfg.batch, n_updates)

    def pr3_chunk(params, opt_state, buf, result, seen):
        st0 = reset_batch(rkeys)
        _, traj = rollout_actor(params, st0, akeys)
        buf = R.buffer_add(buf, R.flatten_transitions(traj, _SAC_FIELDS))
        host = jax.device_get({k: traj[k] for k in ("obs", "reward", "leak",
                                                    "viol")})
        for i in range(num_envs):
            for row in host["obs"][i]:
                seen.add(_legacy_obs_hash(row))
            result.episode_reward.append(float(host["reward"][i].sum()))
            result.episode_leak.append(float(host["leak"][i].sum()))
            result.episode_violation.append(float(host["viol"][i].sum()))
            result.states_explored.append(len(seen))
        if int(buf.size) >= cfg.batch:  # the per-chunk host size sync
            params, opt_state, _ = fused(params, opt_state, buf, ku)
        return params, opt_state, buf

    buf = R.buffer_init(cfg.buffer_size, _sac_example(env, cfg))
    params, opt_state = params0, init_seq(params0)
    for _ in range(2):  # warm the jits AND fill past batch size so every
        # timed chunk runs its update scan (needs num_envs*T*2 >= batch)
        params, opt_state, buf = pr3_chunk(params, opt_state, buf,
                                           TrainResult(), set())
    result, seen = TrainResult(), set()
    t0 = time.perf_counter()
    for _ in range(chunks):
        params, opt_state, buf = pr3_chunk(params, opt_state, buf, result,
                                           seen)
    pr3_eps = episodes / (time.perf_counter() - t0)

    # --- fused chunk: one buffer-donated dispatch per chunk --------------
    upd_joint, init_joint = SAC.make_update(adims, cfg)
    chunk = R.make_train_chunk(
        env, R.uniform_policy(adims), R.sac_policy(adims, cfg), upd_joint,
        hist_len=cfg.hist_len, fields=_SAC_FIELDS, batch_size=cfg.batch,
        n_updates=n_updates,
    )
    buf = R.buffer_init(cfg.buffer_size, _sac_example(env, cfg))
    params, opt_state = params0, init_joint(params0)
    train = jnp.asarray(True)
    for _ in range(2):  # warm + fill, mirroring the PR-3 side
        params, opt_state, buf, m = chunk(params, opt_state, buf, rkeys,
                                          akeys, ku, train)
        _reduced_chunk_metrics(TrainResult(), set(), jax.device_get(m), 0,
                               episodes, num_envs)
    result, seen = TrainResult(), set()
    t0 = time.perf_counter()
    for c in range(chunks):
        params, opt_state, buf, m = chunk(params, opt_state, buf, rkeys,
                                          akeys, ku, train)
        _reduced_chunk_metrics(result, seen, jax.device_get(m),
                               c * num_envs, episodes, num_envs)
    fused_eps = episodes / (time.perf_counter() - t0)

    # --- end-to-end train_sac on the fused engine (compiles included) ----
    t0 = time.perf_counter()
    train_sac(env, cfg, episodes=episodes, warmup_episodes=num_envs,
              num_envs=num_envs, seed=1)
    e2e_eps = episodes / (time.perf_counter() - t0)

    return {
        "num_envs": num_envs,
        "chunks": chunks,
        "episodes": episodes,
        "episodes_per_sec": {"pr3_chunk_loop": pr3_eps,
                             "fused_chunk": fused_eps,
                             "train_sac_end_to_end": e2e_eps},
        "fused_chunk_speedup": fused_eps / pr3_eps,
    }


SWEEP_QS = (0.3, 0.45, 0.6, 0.75, 0.9)


def _time_scenario_sweep(env, params, cfg, episodes: int, key):
    """5-point ``monitor_prob`` sweep: per-point re-jit loop (the seed's
    pattern - fresh env + fresh jits per point) vs ONE stacked-scenario
    evaluation through the population evaluator. Returns wall-clocks,
    retrace counts, and the speedup. Both sides time end-to-end including
    compiles - that is precisely the cost the scenario API removes."""
    k_reset, k_act = jax.random.split(key)
    rkeys = jax.random.split(k_reset, episodes)
    akeys = jax.random.split(k_act, episodes)

    # --- baseline: re-instantiate env + rebuild jits per sweep point -----
    t0 = time.perf_counter()
    loop_leak, loop_traces = [], 0
    for q in SWEEP_QS:
        env_q = MHSLEnv(profile=env.profile,
                        net=replace(env.net, monitor_prob=q))
        rollout = R.make_batched_rollout(
            env_q, R.sac_policy(env_q.action_dims, cfg), cfg.hist_len)
        st0 = R.make_batched_reset(env_q)(rkeys)
        _, traj = rollout(params, st0, akeys)
        loop_leak.append(float(traj["leak"].sum()) / episodes)
        loop_traces += rollout.trace_count[0]
    dt_loop = time.perf_counter() - t0

    # --- scenario API: one compiled eval step for the whole grid ---------
    evaluator = make_population_evaluator(
        env, R.sac_policy(env.action_dims, cfg), cfg.hist_len)
    scens = stack_scenarios(
        scenario_grid(env.scenario(), monitor_prob=list(SWEEP_QS)))
    t0 = time.perf_counter()
    out = evaluator(params, rkeys, akeys, scens)
    sweep_leak = [float(x) for x in jax.device_get(out["leak"])]
    dt_sweep = time.perf_counter() - t0

    return {
        "points": len(SWEEP_QS),
        "episodes_per_point": episodes,
        "per_point_rejit_s": dt_loop,
        "scenario_sweep_s": dt_sweep,
        "sweep_speedup": dt_loop / dt_sweep,
        "compiles": {"per_point_loop": loop_traces,
                     "scenario_sweep": evaluator.trace_count[0]},
        "leak": {"per_point_loop": loop_leak, "scenario_sweep": sweep_leak},
    }


SHARDED_DEVICES = 4

# Runs in a clean subprocess with a forced host device count (the parent
# process has already initialized its backend, typically with 1 device).
# Measures the SAME population rollout twice: default single-device
# placement vs sharded over a population mesh spanning every device.
_SHARDED_SNIPPET = """
import json, time
import jax
from repro.core.agents import rollout as R
from repro.core.agents import sac as SAC
from repro.core.env import MHSLEnv
from repro.core.profiles import resnet101_profile
from repro.core.scenario import (
    make_population_rollout, scenario_grid, stack_scenarios,
)
from repro.distribution import population as PD
from repro.distribution.mesh import make_population_mesh

N, NUM_ENVS, CHUNKS = {n}, {num_envs}, {chunks}
env = MHSLEnv(profile=resnet101_profile(batch=1))
cfg = SAC.SACConfig()
key = jax.random.PRNGKey(0)
key, k0, kr, ka = jax.random.split(key, 4)
params = SAC.init_agent(k0, env.obs_dim, env.action_dims, cfg)
rollout = make_population_rollout(env, R.sac_policy(env.action_dims, cfg),
                                  cfg.hist_len)
scens = stack_scenarios(scenario_grid(
    env.scenario(), monitor_prob=[0.3 + 0.6 * i / (N - 1) for i in range(N)]))
rkeys = jax.random.split(kr, NUM_ENVS)
akeys = jax.random.split(ka, NUM_ENVS)


def measure(params, rkeys, akeys, scens):
    jax.block_until_ready(rollout(params, rkeys, akeys, scens))  # compile
    t0 = time.perf_counter()
    for _ in range(CHUNKS):
        _, traj = rollout(params, rkeys, akeys, scens)
    jax.block_until_ready(traj["reward"])
    return CHUNKS * N * NUM_ENVS * env.episode_len / (time.perf_counter() - t0)


single_sps = measure(params, rkeys, akeys, scens)
mesh = make_population_mesh()
sharded_sps = measure(
    PD.replicate(params, mesh), PD.replicate(rkeys, mesh),
    PD.replicate(akeys, mesh), PD.shard_population(scens, mesh, N))
print("RESULT " + json.dumps({{
    "devices": len(jax.devices()), "scenarios": N, "num_envs": NUM_ENVS,
    "episode_len": env.episode_len,
    "env_steps_per_sec": {{"single_device": single_sps,
                           "sharded": sharded_sps}},
    "sharded_speedup": sharded_sps / single_sps,
}}))
"""


def _time_sharded_population(bench: BenchConfig):
    """Sharded-population rollout throughput on a forced multi-device host."""
    # scenarios must divide SHARDED_DEVICES even in smoke mode, else the
    # placement falls back to replication and the sharded path goes untested
    n, num_envs = (4, 4) if bench.smoke else (4, 8)
    chunks = 2 if bench.smoke else (6 if bench.quick else 20)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={SHARDED_DEVICES}"
    )
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    code = _SHARDED_SNIPPET.format(n=n, num_envs=num_envs, chunks=chunks)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=1200, env=env, cwd=REPO_ROOT)
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded-population subprocess failed:\n{out.stderr[-3000:]}"
        )
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def main(bench: BenchConfig = BenchConfig(), seed: int = 0):
    env = MHSLEnv(profile=resnet101_profile(batch=1))
    cfg = SAC.SACConfig()
    # the seed's update path for the legacy-tracking metrics, so
    # `updates_per_sec` keeps its historical meaning (dispatch overhead
    # with an identical update fn on both sides)
    seq_update, seq_init = SAC.make_update(env.action_dims,
                                           replace(cfg, joint_update=False))
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    params = SAC.init_agent(k0, env.obs_dim, env.action_dims, cfg)
    opt_state = seq_init(params)

    legacy_eps = 3 if bench.smoke else (20 if bench.quick else 60)
    engine_chunks = 3 if bench.smoke else (20 if bench.quick else 60)
    n_updates = 8 if bench.smoke else (50 if bench.quick else 200)
    chunk_chunks = 2 if bench.smoke else (6 if bench.quick else 16)
    chunk_envs = 8 if bench.smoke else NUM_ENVS

    key, k1, k2 = jax.random.split(key, 3)
    legacy_sps = _time_legacy_rollout(env, params, cfg, legacy_eps, k1)
    engine_sps = _time_engine_rollout(env, params, cfg, engine_chunks, k2)
    rollout_speedup = engine_sps / legacy_sps

    np_buf, dev_buf = _fill_buffers(env, params, cfg)
    legacy_ups = _time_legacy_updates(seq_update, params, opt_state, np_buf,
                                      cfg, n_updates)
    engine_ups = _time_engine_updates(seq_update, params, opt_state, dev_buf,
                                      cfg, n_updates)
    update_speedup = engine_ups / legacy_ups

    # the update ladder feeds a CI gate, so even smoke mode measures
    # enough updates to amortize dispatch noise
    update_path = _time_update_paths(env, params, np_buf, dev_buf, cfg,
                                     max(n_updates, 32))
    key, kc = jax.random.split(key)
    fused_chunk = _time_chunk_loops(env, cfg, chunk_chunks, chunk_envs, kc)

    key, k3 = jax.random.split(key)
    sweep = _time_scenario_sweep(env, params, cfg,
                                 2 if bench.smoke else
                                 (8 if bench.quick else 32), k3)

    sharded = _time_sharded_population(bench)

    emit_csv_row("throughput/legacy_env_steps_per_sec", 1e6 / legacy_sps,
                 f"env_steps_per_sec={legacy_sps:.0f}")
    emit_csv_row("throughput/engine_env_steps_per_sec", 1e6 / engine_sps,
                 f"env_steps_per_sec={engine_sps:.0f} num_envs={NUM_ENVS}")
    emit_csv_row("throughput/legacy_updates_per_sec", 1e6 / legacy_ups,
                 f"updates_per_sec={legacy_ups:.0f}")
    emit_csv_row("throughput/engine_updates_per_sec", 1e6 / engine_ups,
                 f"updates_per_sec={engine_ups:.0f}")
    joint_ups = update_path["updates_per_sec"]["fused_joint"]
    emit_csv_row("throughput/update_path", 1e6 / joint_ups,
                 f"updates_per_sec={joint_ups:.0f} "
                 f"joint_speedup_vs_legacy="
                 f"{update_path['joint_speedup_vs_legacy']:.2f}x")
    fc = fused_chunk["episodes_per_sec"]
    emit_csv_row("throughput/fused_chunk", 1e6 / max(fc["fused_chunk"], 1e-9),
                 f"episodes_per_sec={fc['fused_chunk']:.2f} "
                 f"vs_pr3={fused_chunk['fused_chunk_speedup']:.2f}x "
                 f"train_sac={fc['train_sac_end_to_end']:.2f}")
    emit_csv_row("throughput/scenario_sweep", 1e6 * sweep["scenario_sweep_s"],
                 f"sweep_speedup={sweep['sweep_speedup']:.1f}x "
                 f"compiles={sweep['compiles']['scenario_sweep']}"
                 f"(vs {sweep['compiles']['per_point_loop']})")
    emit_csv_row(
        "throughput/sharded_population",
        1e6 / max(sharded["env_steps_per_sec"]["sharded"], 1e-9),
        f"env_steps_per_sec={sharded['env_steps_per_sec']['sharded']:.0f} "
        f"devices={sharded['devices']} scenarios={sharded['scenarios']} "
        f"num_envs={sharded['num_envs']} "
        f"speedup_vs_1dev={sharded['sharded_speedup']:.2f}x")
    emit_csv_row("throughput/summary", 0.0,
                 f"rollout_speedup={rollout_speedup:.1f}x "
                 f"update_speedup={update_speedup:.1f}x "
                 f"scenario_sweep_speedup={sweep['sweep_speedup']:.1f}x")

    payload = {
        "backend": jax.default_backend(),
        "num_envs": NUM_ENVS,
        "env_steps_per_sec": {"legacy": legacy_sps, "engine": engine_sps},
        "updates_per_sec": {"legacy": legacy_ups, "engine": engine_ups},
        "rollout_speedup": rollout_speedup,
        "update_speedup": update_speedup,
        "update_path": update_path,
        "fused_chunk": fused_chunk,
        "scenario_sweep": sweep,
        "sharded_population": sharded,
    }
    save_json("throughput", payload)
    if bench.smoke:  # smoke numbers are for rot detection, not tracking
        return payload
    refresh = os.environ.get("BENCH_THROUGHPUT_REFRESH") == "1"
    if refresh or not os.path.exists(BASELINE_PATH):
        with open(BASELINE_PATH, "w") as f:
            json.dump(payload, f, indent=1, default=float)
    else:
        # the baseline is write-once for existing metrics, but a newly
        # added metric gets recorded into it the first time it is measured
        with open(BASELINE_PATH) as f:
            baseline = json.load(f)
        missing = [k for k in payload if k not in baseline]
        if missing:
            for k in missing:
                baseline[k] = payload[k]
            with open(BASELINE_PATH, "w") as f:
                json.dump(baseline, f, indent=1, default=float)
    return payload


if __name__ == "__main__":
    main()
