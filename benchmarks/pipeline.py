"""Split-executor benchmarks: 1F1B vs fill-drain, masked vs padded splits,
overlapped vs synchronous stage handoff, and vectorized plan scoring vs
the per-plan python loop.

Four measurements:

* ``pipeline_schedule`` - train-step wall clock of the fill-drain
  (GPipe + ``jax.grad``) reference vs the 1F1B executor on an S-stage
  mesh at M in {1, 4, 8} microbatches, for an EVEN split (padding-free,
  isolates the schedule/tick win) and an UNEVEN RL-style split (where
  fill-drain additionally pays padded max-length matmuls that 1F1B's
  active-length masking skips). Runs in a subprocess with a forced host
  device count (the parent backend typically has 1 device). Alongside
  the wall clocks it records the STRUCTURAL accounting - tick counts,
  padded vs active block-applies, bubble fractions - so accelerator
  targets can read the schedule win even where a 2-core CPU host is
  dispatch-bound.
* ``pipeline_transport`` - the 1F1B executor's double-buffered
  (``transport="overlap"``) vs synchronous (``transport="sync"``) stage
  handoff at S in {4, 8}, even and uneven splits, on forced CPU host
  devices. Each row carries the measured wall clock AND the structural
  link-model ratio from ``repro.core.transport.simulate_1f1b`` (the
  per-hop bandwidth/latency physics shared with ``plan_cost``); the
  structural ratio is >= 1 by construction (max <= sum per tick), the
  wall clock shows what a dispatch-bound CPU host realizes of it.
* ``plan_scoring`` - ``splitting.score_plans`` (one jitted vmap over the
  stacked enumeration) vs the per-plan ``plan_cost`` python loop at the
  acceptance point L=24, S=4 (1771 plans). Both sides warm.
* CI gate input: bench-smoke reads the per-run JSON and fails if
  1F1B/fill-drain < 1 at the largest measured M, or if the overlapped
  transport falls behind the synchronous one (structural ratio < 1, or
  wall clock below the shared-runner noise floor).

New baseline keys are recorded write-once into ``BENCH_throughput.json``
(never in ``--smoke``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import (
    BenchConfig, emit_csv_row, record_baseline, save_json, REPO_ROOT,
)


def _structural(schedule: str, m: int, lens) -> dict:
    """Tick counts / block-unit work / bubble fraction of one schedule.

    Block units weigh a backward block-apply at 2x a forward one. The
    fill-drain reference runs every stage padded to ``max(lens)`` blocks
    for ``M + S - 1`` forward ticks plus the same again reversed under
    ``jax.grad``; it also evaluates the LM head + loss on EVERY stage
    every forward tick (counted separately). 1F1B runs ``M + 2(S-1)``
    ticks; each microbatch costs a stage 1 forward + 1 rematerialized
    forward + 2 backward units over its ACTIVE length only (the last
    stage skips the standalone forward slot), and the head runs once per
    microbatch.
    """
    s = len(lens)
    max_len = max(lens)
    total = sum(lens)
    if schedule == "fill_drain":
        ticks = 2 * (m + s - 1)
        block_units = 3 * (m + s - 1) * s * max_len
        head_evals = (m + s - 1) * s
        bubble = (s - 1) / (s - 1 + m)
    else:
        ticks = m + 2 * (s - 1)
        block_units = m * (4 * total - lens[-1])
        head_evals = m
        bubble = 2 * (s - 1) / (m + 2 * (s - 1))
    return {"ticks": ticks, "block_units": block_units,
            "head_evals": head_evals, "bubble_fraction": bubble}


# Runs in a clean subprocess with a forced host device count (the parent
# has already initialized its 1-device CPU backend). Prints one RESULT
# json line with per-(split, M, schedule) step times.
_SCHEDULE_SNIPPET = """
import json, os, time
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace

from repro.configs import get_config
from repro.models import init_params
from repro.core.pipeline import (
    PipelineConfig, make_stage_mesh, pipeline_step_fn, stage_lengths,
)

SPEC = json.loads(os.environ["PIPE_BENCH_SPEC"])
cfg = replace(get_config(SPEC["arch"]).reduced(), num_layers=SPEC["layers"])
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_stage_mesh(SPEC["stages"])
rng = np.random.default_rng(0)
out = []
for split_name, bounds in SPEC["splits"]:
    bounds = tuple(bounds)
    for m in SPEC["microbatches"]:
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (m * SPEC["mb_rows"], SPEC["seq"])),
            jnp.int32)
        labels = jnp.asarray(
            rng.integers(0, cfg.vocab_size, tokens.shape), jnp.int32)
        row = {"split": split_name, "boundaries": list(bounds), "m": m,
               "lens": list(stage_lengths(bounds))}
        for sched in ("fill_drain", "1f1b"):
            step = jax.jit(pipeline_step_fn(
                cfg, mesh, bounds, m, pipe=PipelineConfig(schedule=sched)))
            t0 = time.perf_counter()
            l, g = step(params, tokens, labels)
            jax.block_until_ready(jax.tree.leaves(g)[0])
            compile_s = time.perf_counter() - t0
            best = float("inf")  # best-of-2 windows: shared-runner noise
            for _ in range(2):
                t0 = time.perf_counter()
                for _ in range(SPEC["reps"]):
                    l, g = step(params, tokens, labels)
                jax.block_until_ready(jax.tree.leaves(g)[0])
                best = min(best, (time.perf_counter() - t0) / SPEC["reps"])
            row[sched] = {"step_s": best, "compile_s": compile_s,
                          "loss": float(l)}
        row["speedup_1f1b"] = row["fill_drain"]["step_s"] / row["1f1b"]["step_s"]
        out.append(row)
print("RESULT " + json.dumps(out))
"""


def _time_schedules(bench: BenchConfig):
    if bench.smoke:
        spec = {"arch": "qwen2.5-3b", "layers": 4, "stages": 2,
                "splits": [["uneven", [3, 4]]], "microbatches": [1, 4],
                "mb_rows": 2, "seq": 16, "reps": 2}
    else:
        spec = {"arch": "qwen2.5-3b", "layers": 8, "stages": 4,
                "splits": [["even", [2, 4, 6, 8]], ["uneven", [5, 6, 7, 8]]],
                "microbatches": [1, 4, 8], "mb_rows": 2, "seq": 32,
                "reps": 3 if bench.quick else 6}
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={spec['stages']}"
    )
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    env["PIPE_BENCH_SPEC"] = json.dumps(spec)
    out = subprocess.run([sys.executable, "-c", _SCHEDULE_SNIPPET],
                         capture_output=True, text=True, timeout=3000,
                         env=env, cwd=REPO_ROOT)
    if out.returncode != 0:
        raise RuntimeError(
            f"pipeline-schedule subprocess failed:\n{out.stderr[-3000:]}")
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rows = json.loads(line[len("RESULT "):])
    for row in rows:
        for sched in ("fill_drain", "1f1b"):
            row[sched]["structural"] = _structural(sched, row["m"],
                                                   row["lens"])
    return {"spec": spec, "rows": rows}


# Times the SAME 1F1B program under both transports in one subprocess
# (one forced device count per S). Prints one RESULT json line.
_TRANSPORT_SNIPPET = """
import json, os, time
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace

from repro.configs import get_config
from repro.models import init_params
from repro.core.pipeline import (
    PipelineConfig, make_stage_mesh, pipeline_step_fn, stage_lengths,
)

SPEC = json.loads(os.environ["PIPE_BENCH_SPEC"])
mesh = make_stage_mesh(SPEC["stages"])
rng = np.random.default_rng(0)
out = []
for split_name, bounds in SPEC["splits"]:
    bounds = tuple(bounds)
    # each split carries its own layer count (bounds end at num_layers;
    # S=8 has no even split of 9 layers)
    cfg = replace(get_config(SPEC["arch"]).reduced(), num_layers=bounds[-1])
    params = init_params(jax.random.PRNGKey(0), cfg)
    m = SPEC["microbatches"]
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (m * SPEC["mb_rows"], SPEC["seq"])),
        jnp.int32)
    labels = jnp.asarray(
        rng.integers(0, cfg.vocab_size, tokens.shape), jnp.int32)
    row = {"split": split_name, "boundaries": list(bounds), "m": m,
           "lens": list(stage_lengths(bounds))}
    steps, best = {}, {}
    for tr in ("sync", "overlap"):
        steps[tr] = jax.jit(pipeline_step_fn(
            cfg, mesh, bounds, m, pipe=PipelineConfig(transport=tr)))
        t0 = time.perf_counter()
        l, g = steps[tr](params, tokens, labels)
        jax.block_until_ready(jax.tree.leaves(g)[0])
        row[tr] = {"compile_s": time.perf_counter() - t0, "loss": float(l)}
        best[tr] = float("inf")
    # best-of-5 INTERLEAVED windows: alternating the two transports inside
    # each window cancels machine-state drift (turbo, cache warmth) that a
    # sequential sync-then-overlap timing folds into the reported ratio
    for _ in range(5):
        for tr in ("sync", "overlap"):
            t0 = time.perf_counter()
            for _ in range(SPEC["reps"]):
                l, g = steps[tr](params, tokens, labels)
            jax.block_until_ready(jax.tree.leaves(g)[0])
            best[tr] = min(best[tr], (time.perf_counter() - t0) / SPEC["reps"])
    for tr in ("sync", "overlap"):
        row[tr]["step_s"] = best[tr]
    # wall ratio only: forced-CPU devices run synchronous collective-permute
    # (no async start/done), so this is parity +/- timer noise by
    # construction; the structural ratio is attached host-side as
    # row["speedup_overlap"] (see _time_transport)
    row["wall_speedup_overlap"] = row["sync"]["step_s"] / row["overlap"]["step_s"]
    out.append(row)
print("RESULT " + json.dumps(out))
"""


def _transport_model_ratio(stages: int, bounds, m: int, layers: int,
                           seed: int = 0) -> dict:
    """Structural overlap/sync ratio under the per-hop link model.

    Builds the SAME Eq. 8-11 physics the plan oracle prices
    (``plan_transport_model`` wraps ``plan_cost_parts``) on a heterogeneous
    link ladder - hop k at a different TDMA bandwidth plus a fixed link
    latency - and simulates both 1F1B transports. The ratio is >= 1 by
    construction: an overlapped tick pays max(compute, in-flight hop)
    where the synchronous tick pays the sum.
    """
    from repro.configs import get_config
    from repro.core.channel import NetworkConfig
    from repro.core.profiles import transformer_profile
    from repro.core.splitting import SplitPlan
    from repro.core.transport import plan_transport_model, simulate_1f1b
    from dataclasses import replace

    # heterogeneous ladder: every other hop at half bandwidth, 2 ms latency
    hop_bw = tuple(1e6 if k % 2 == 0 else 5e5 for k in range(stages - 1))
    net = NetworkConfig(num_devices=max(8, stages), max_split=stages,
                        hop_bandwidth=hop_bw, hop_latency=2e-3)
    cfg = replace(get_config("qwen2.5-3b").reduced(), num_layers=layers)
    prof = transformer_profile(cfg, batch=1, seq=512)
    u = net.num_devices
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, net.area_m, (u + 1, 2))
    devices = tuple(int(d) for d in list(range(stages - 1)) + [u])
    p_tx = np.full((stages - 1,), 0.5)
    decoy = np.zeros((stages - 1, u + 1))
    model = plan_transport_model(prof, SplitPlan(tuple(bounds), devices),
                                 pos, p_tx, decoy, net)
    sync = simulate_1f1b(model, m, transport="sync")
    ovl = simulate_1f1b(model, m, transport="overlap")
    return {
        "hop_bandwidth_hz": list(hop_bw), "hop_latency_s": net.hop_latency,
        "sync_total_s": sync["total_s"], "overlap_total_s": ovl["total_s"],
        "model_speedup": sync["total_s"] / ovl["total_s"],
        "bubble_fraction": ovl["bubble_fraction"],
    }


def _time_transport(bench: BenchConfig):
    """Overlapped vs synchronous handoff at S in {4, 8} (subprocess per S).

    Two ratios per split, both recorded:

    * ``speedup_overlap`` (headline, >= 1 by construction): the
      STRUCTURAL overlap/sync ratio under the per-hop link model - each
      overlapped tick pays ``max(compute, in-flight hop)`` where the
      synchronous tick pays the sum, priced by the same Eq. 8-11 physics
      as ``plan_cost`` (``core.transport.simulate_1f1b``). This is what
      the wire delivers on a backend with async collectives.
    * ``wall_speedup_overlap``: the measured wall ratio on the forced-CPU
      stage mesh. XLA's CPU backend emits only SYNCHRONOUS
      collective-permute (no ``-start``/``-done`` pairs - pinned by
      ``test_overlap_issues_no_more_collectives_than_sync``), so wall is
      parity +/- timer noise here; it guards against the overlapped
      schedule REGRESSING (extra copies, bigger carries), not for the
      overlap win itself.
    """
    if bench.smoke:
        cases = [{"stages": 2, "microbatches": 4,
                  "splits": [["even", [2, 4]], ["uneven", [3, 4]]],
                  "mb_rows": 2, "seq": 16, "reps": 2}]
    else:
        cases = [
            {"stages": 4, "microbatches": 8,
             "splits": [["even", [2, 4, 6, 8]], ["uneven", [5, 6, 7, 8]]],
             "mb_rows": 2, "seq": 32, "reps": 3 if bench.quick else 6},
            {"stages": 8, "microbatches": 8,
             "splits": [["even", [1, 2, 3, 4, 5, 6, 7, 8]],
                        ["uneven", [2, 3, 4, 5, 6, 7, 8, 9]]],
             "mb_rows": 2, "seq": 32, "reps": 3 if bench.quick else 6},
        ]
    out = []
    for spec in cases:
        spec = dict(spec, arch="qwen2.5-3b")
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={spec['stages']}"
        )
        env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
        env["PIPE_BENCH_SPEC"] = json.dumps(spec)
        res = subprocess.run([sys.executable, "-c", _TRANSPORT_SNIPPET],
                             capture_output=True, text=True, timeout=3000,
                             env=env, cwd=REPO_ROOT)
        if res.returncode != 0:
            raise RuntimeError(
                f"pipeline-transport subprocess failed:\n{res.stderr[-3000:]}")
        line = [l for l in res.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        rows = json.loads(line[len("RESULT "):])
        for row in rows:
            row["model"] = _transport_model_ratio(
                spec["stages"], row["boundaries"], row["m"],
                layers=row["boundaries"][-1])
            row["speedup_overlap"] = row["model"]["model_speedup"]
        out.append({
            "spec": spec,
            "note": ("speedup_overlap is the structural overlap/sync ratio "
                     "under the per-hop link model (>= 1 by construction; "
                     "what an async backend delivers on the wire); "
                     "wall_speedup_overlap is the measured forced-CPU wall "
                     "ratio, parity +/- noise since the CPU backend runs "
                     "synchronous collective-permute"),
            "rows": rows,
        })
    return out


def _time_plan_scoring(bench: BenchConfig, seed: int):
    from repro.core.channel import NetworkConfig
    from repro.core.profiles import resnet101_profile, transformer_profile
    from repro.configs import get_config
    from repro.core.splitting import (
        SplitPlan, make_plan_scorer, plan_cost, stack_boundaries,
    )
    import jax

    l_layers, s = (10, 3) if bench.smoke else (24, 4)
    net = NetworkConfig()
    u = net.num_devices
    prof = transformer_profile(get_config("qwen2.5-3b"), batch=1, seq=2048)
    prof = prof if prof.num_layers >= l_layers else resnet101_profile(1)
    # score a fixed L-layer prefix enumeration of the profile
    bounds = stack_boundaries(l_layers, s)
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, net.area_m, (u + 1, 2))
    devices = np.concatenate([np.arange(s - 1), [u]]).astype(np.int32)
    p_tx = np.full((s - 1,), 0.5)
    decoy = np.zeros((s - 1, u + 1))
    decoy[:, s] = 0.2

    # --- python loop (the seed's oracle-baseline pattern) ----------------
    def loop():
        out = []
        for b in bounds:
            plan = SplitPlan(tuple(int(x) for x in b), tuple(devices))
            out.append(plan_cost(prof, plan, pos, p_tx, decoy, net))
        return np.asarray(out)

    ref = loop()  # warm the per-op jit caches
    t0 = time.perf_counter()
    ref = loop()
    loop_s = time.perf_counter() - t0

    # --- vectorized: one dispatch over the whole stack -------------------
    scorer = make_plan_scorer(prof)
    t, e = scorer(bounds, devices, pos, p_tx, decoy, net)  # compile
    jax.block_until_ready(e)
    t0 = time.perf_counter()
    t, e = scorer(bounds, devices, pos, p_tx, decoy, net)
    jax.block_until_ready(e)
    vec_s = time.perf_counter() - t0

    err = float(np.abs(np.stack([np.asarray(t), np.asarray(e)], 1) - ref).max()
                / np.abs(ref).max())
    return {
        "layers": l_layers, "stages": s, "plans": int(bounds.shape[0]),
        "plan_cost_loop_s": loop_s, "score_plans_s": vec_s,
        "speedup": loop_s / vec_s, "traces": scorer.trace_count[0],
        "max_rel_err_vs_loop": err,
    }


def main(bench: BenchConfig = BenchConfig(), seed: int = 0,
         force: bool = False):
    sched = _time_schedules(bench)
    transport = _time_transport(bench)
    scoring = _time_plan_scoring(bench, seed)

    for row in sched["rows"]:
        emit_csv_row(
            f"pipeline/{row['split']}_m{row['m']}",
            1e6 * row["1f1b"]["step_s"],
            f"1f1b_step_s={row['1f1b']['step_s']:.3f} "
            f"speedup_vs_fill_drain={row['speedup_1f1b']:.2f}x "
            f"bubble={row['1f1b']['structural']['bubble_fraction']:.2f}"
            f"(vs {row['fill_drain']['structural']['bubble_fraction']:.2f})")
    for case in transport:
        for row in case["rows"]:
            emit_csv_row(
                f"pipeline/transport_s{case['spec']['stages']}_{row['split']}",
                1e6 * row["overlap"]["step_s"],
                f"overlap_step_s={row['overlap']['step_s']:.3f} "
                f"speedup_vs_sync={row['speedup_overlap']:.2f}x "
                f"wall={row['wall_speedup_overlap']:.2f}x")
    emit_csv_row(
        "pipeline/plan_scoring", 1e6 * scoring["score_plans_s"],
        f"plans={scoring['plans']} speedup={scoring['speedup']:.1f}x "
        f"traces={scoring['traces']}")

    payload = {"pipeline_schedule": sched, "pipeline_transport": transport,
               "plan_scoring": scoring}
    save_json("pipeline", payload)
    if not bench.smoke:
        record_baseline(payload, force=force)
    return payload


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true",
                    help="re-record existing BENCH_throughput.json keys")
    ap.add_argument("--full", action="store_true",
                    help="non-quick rep counts")
    a = ap.parse_args()
    main(BenchConfig(quick=not a.full), force=a.force)
