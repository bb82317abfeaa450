"""Smoke check on the chip: the planner, the 1F1B split executor and split
serving, each driven once through the entry points a user calls.

    python chip_smoke.py              # one chip: the three paths
    python chip_smoke.py --chips 4    # four chips: 1F1B and split serving
                                      # over a 4-stage mesh, each against
                                      # its single-device reference

Everything runs in this one process: a chip belongs to one process, so a
child could not reach it. When JAX finds no TPU the script exits non-zero
before any phase and prints no result. The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``; no
phase's failure is caught. Times printed on earlier lines are smoke time
(compilation included), not benchmark results.

One chip, qwen2.5-3b (weights random, from ``--seed``):

* planner: ICM-CA SAC on the 36-layer profile, ``num_envs=64``, a few
  donated train chunks, then the learned split plan;
* executor: a few AdamW steps of ``pipeline_step_fn`` (1F1B) on a 1-stage
  mesh at published widths, depth cut to 4 layers (see ``EXEC_LAYERS``);
* serving: ``ServingService`` on all 36 layers in bf16, 16 requests.

Four chips: 1F1B over the uneven split (2,3,6,8) of an 8-layer cut against
``value_and_grad`` of ``models.loss_fn``, and ``PipelineRunner`` over
(9,18,27,36) against ``SingleDeviceRunner`` on the same requests.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"
# Training state at 36 layers is f32 params + grads + two AdamW moments:
# 16 B x 3.09 B params ~ 49 GB, three times a v5e's 16 GB. 4 layers
# (311 M tied embedding + 4 x 77.1 M) is 0.62 B params ~ 9.9 GB; with the
# step's gradients and activations (4x512 tokens in 4 microbatches) the
# compiled program takes ~15.3 of the chip's 15.75 GiB.
EXEC_LAYERS = 4
EXEC_REASON = ("f32 params+grads+2 AdamW moments at 36 layers ~49 GB > "
               "16 GB HBM; 4 layers = 0.62 B params ~9.9 GB")

# --- tolerances -----------------------------------------------------------
# 1-chip executor: the first 1F1B loss against models.loss_fn's forward on
# the same params and batch. Both run bf16 activations with different op
# orders; the loss is a mean over 2048 tokens, so per-token bf16 rounding
# (2^-8 relative) averages down. 1e-2 relative leaves margin for that and
# still catches a wrong layer, head or label alignment (O(1) changes).
EXEC_LOSS_RTOL = 1e-2
# 4-chip 1F1B parity runs both sides in f32 under "highest" matmul
# precision (the TPU otherwise runs f32 matmuls as one bf16 pass), so the
# only differences are summation orders: ~1e-6 relative in practice
# (2e-5 is the CPU tests' gate). 1e-4 on the loss and 1e-3 of each grad
# leaf's largest entry leave 5-50x margin; a misrouted hop or stage is
# O(1).
PARITY_LOSS_RTOL = 1e-4
PARITY_GRAD_TOL = 1e-3
# Greedy tokens are compared where the reference's top-2 logit margin is
# clear of the logit error: one bf16 ulp at magnitude m is m * 2^-7, and
# a token may legitimately flip on a near-tie. Same runner, same shapes
# (1-chip serving): 4 ulps of the largest logit.
SAME_CODE_LOGIT_TOL = 2.0 ** -5
# Pipeline vs single device (4-chip serving): 36 bf16 layers compiled
# into two different programs; differences grow with depth. 5% of the
# largest logit still separates a correct split from a wrong one (a
# wrong layer order or hop gives unrelated logits).
PIPE_LOGIT_RTOL = 5e-2


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def memory_line(devices) -> str:
    """Peak and current bytes per device, as the backend reports them."""
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        peak, now = st.get("peak_bytes_in_use"), st.get("bytes_in_use")
        if peak is None:
            parts.append(f"dev{d.id}: not reported")
        else:
            parts.append(f"dev{d.id}: peak_bytes_in_use={peak} "
                         f"({peak / 2**30:.2f} GiB) bytes_in_use={now}")
    return "; ".join(parts)


def greedy_check(ref_logits, tokens, tol):
    """Tokens must equal the reference argmax wherever its top-2 margin
    exceeds ``2 * tol``. Returns (agreeing, clear-margin rows, rows)."""
    import numpy as np

    top2 = np.sort(ref_logits, axis=-1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > 2 * tol
    agree = ref_logits.argmax(axis=-1) == np.asarray(tokens)
    bad = np.nonzero(clear & ~agree)[0]
    if bad.size:
        raise AssertionError(
            f"greedy token differs from the reference on rows {bad.tolist()} "
            f"although the reference margin exceeds 2 x {tol:.4g}")
    return int(agree.sum()), int(clear.sum()), len(agree)


def last_prefill_logits(runner, params, trace, prompt_pad, cache_len):
    """Reference logits at each request's last prompt position: one jitted
    batched prefill through ``runner`` (the engine's own prefill code)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    prompts = np.zeros((len(trace), prompt_pad), np.int32)
    for i, r in enumerate(trace):
        prompts[i, :r.plen] = r.prompt
    plens = np.asarray([r.plen for r in trace])
    caches = runner.init_caches(len(trace), cache_len)
    logits, _ = jax.jit(runner.prefill)(params, caches, jnp.asarray(prompts))
    last = logits[jnp.arange(len(trace)), jnp.asarray(plens) - 1]
    return np.asarray(last.astype(jnp.float32))


def check_completions(res, trace, vocab):
    import numpy as np

    if res["num_requests"] != len(trace):
        raise AssertionError(
            f"{res['num_requests']}/{len(trace)} requests completed")
    for r in trace:
        toks = np.asarray(res["completions"][r.rid])
        if toks.shape != (r.gen_target,):
            raise AssertionError(f"request {r.rid}: {toks.shape[0]} tokens, "
                                 f"wanted {r.gen_target}")
        if toks.min() < 0 or toks.max() >= vocab:
            raise AssertionError(f"request {r.rid}: token outside the vocab")
    return sum(r.gen_target for r in trace)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_planner(model_cfg, seed: int, *, num_envs: int = 64,
                  chunks: int = 3) -> None:
    import numpy as np

    from repro.api import (MHSLEnv, NetworkConfig, SACConfig, rollout_plan,
                           train_sac, transformer_profile)

    prof = transformer_profile(model_cfg, batch=1, seq=128)
    env = MHSLEnv(profile=prof, net=NetworkConfig(max_split=4))
    cfg = SACConfig()
    t0 = time.perf_counter()
    res = train_sac(env, cfg, episodes=chunks * num_envs, seed=seed,
                    warmup_episodes=num_envs, num_envs=num_envs)
    smoke_s = time.perf_counter() - t0
    rewards = np.asarray(res.episode_reward)
    if rewards.shape != (chunks * num_envs,) or not np.isfinite(rewards).all():
        raise AssertionError(f"episode rewards {rewards.shape}, finite="
                             f"{np.isfinite(rewards).all()}")
    if not res.metrics:
        raise AssertionError("no train chunk ran its update scan")
    if res.trace_count != 1:
        raise AssertionError(f"train chunk compiled {res.trace_count} times")
    bounds, devices, leaked, t_r, e_r = rollout_plan(env, res.params, cfg,
                                                     seed=seed)
    if not all(0 <= b <= prof.num_layers for b in bounds):
        raise AssertionError(f"plan boundaries {bounds} outside the model")
    log("planner", f"{len(rewards)} episodes ({chunks} chunks x {num_envs} "
                   f"envs, {len(res.metrics)} with updates) on the "
                   f"{prof.num_layers}-layer {model_cfg.name} profile")
    log("planner", f"final reward {rewards[-1]:.4f}; mean of last chunk "
                   f"{rewards[-num_envs:].mean():.4f}")
    log("planner", f"plan boundaries={bounds} devices={devices} "
                   f"leaked={leaked:.4f} budget left T_R={t_r:.4f}s "
                   f"E_R={e_r:.4f}J")
    log("planner", f"train chunk trace_count={res.trace_count}; smoke time "
                   f"{smoke_s:.1f}s (compile included)")


def phase_executor(full_cfg, seed: int, *, layers: int = EXEC_LAYERS,
                   rows: int = 4, seq: int = 512, steps: int = 3,
                   n_micro: int = 4, reason: str = EXEC_REASON) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import (PipelineConfig, adamw, init_params,
                           make_stage_mesh, pipeline_step_fn)
    from repro.models.model import loss_fn
    from repro.optim.optimizers import apply_updates

    cfg = replace(full_cfg, num_layers=layers)
    log("executor", f"reduced: num_layers {full_cfg.num_layers}→{layers} "
                    f"({reason})")
    mesh = make_stage_mesh(1)
    step_fn = pipeline_step_fn(cfg, mesh, (layers,), n_micro,
                               pipe=PipelineConfig())
    opt = adamw(3e-4, max_grad_norm=1.0)

    def train_step(params, opt_state, tokens, labels):
        loss, grads = step_fn(params, tokens, labels)
        ups, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, ups), opt_state, loss

    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    opt_state = jax.jit(opt.init)(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, seq)),
                         jnp.int32)
    ref_loss = float(jax.jit(loss_fn, static_argnums=2)(
        params, {"tokens": tokens, "labels": labels}, cfg)[1][0])
    step = jax.jit(train_step, donate_argnums=(0, 1))
    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(loss))
        log("executor", f"step {i} loss {losses[-1]:.6f}")
    smoke_s = time.perf_counter() - t0
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite 1F1B loss: {losses}")
    if abs(losses[0] - ref_loss) > EXEC_LOSS_RTOL * abs(ref_loss):
        raise AssertionError(f"1F1B loss {losses[0]} vs loss_fn {ref_loss}")
    log("executor", f"{cfg.name} d_model={cfg.d_model} d_ff={cfg.d_ff} "
                    f"vocab={cfg.vocab_size} x {layers} layers = {n_params} "
                    f"params; batch {rows}x{seq} tokens, {n_micro} "
                    f"microbatches, 1 stage")
    log("executor", f"step-0 loss {losses[0]:.6f} vs models.loss_fn "
                    f"{ref_loss:.6f} (rtol {EXEC_LOSS_RTOL})")
    log("executor", f"{steps} steps, smoke time {smoke_s:.1f}s "
                    f"(compile included)")
    log("executor", memory_line(jax.devices()[:1]))


def phase_serving(serve_cfg, seed: int, *, n_requests: int = 16) -> None:
    import jax

    from repro.serving import ServingService, poisson_trace

    svc = ServingService(serve_cfg)
    vocab = svc.model_cfg.vocab_size
    trace = poisson_trace(n_requests=n_requests, rate_per_sec=8.0,
                          vocab_size=vocab,
                          plen_range=(4, serve_cfg.prompt_pad),
                          gen_range=(4, serve_cfg.max_new), seed=seed)
    res = svc.run(trace)
    tokens = check_completions(res, trace, vocab)
    traces = len(svc.step.trace_count)
    if traces != 1:
        raise AssertionError(f"engine step compiled {traces} times")
    ref = last_prefill_logits(svc.runner, svc.params, trace,
                              serve_cfg.prompt_pad,
                              serve_cfg.prompt_pad + serve_cfg.max_new)
    first = [int(res["completions"][r.rid][0]) for r in trace]
    tol = SAME_CODE_LOGIT_TOL * float(abs(ref).max())
    agree, clear, n = greedy_check(ref, first, tol)
    m = svc.model_cfg
    log("serving", f"{m.name} reduced={serve_cfg.reduced}: "
                   f"{m.num_layers} layers, "
                   f"d_model={m.d_model}, vocab={m.vocab_size}, "
                   f"{serve_cfg.compute_dtype}; {serve_cfg.num_slots} slots, "
                   f"prompt_pad={serve_cfg.prompt_pad}, "
                   f"max_new={serve_cfg.max_new}")
    log("serving", f"requests {res['num_requests']}/{n_requests} complete, "
                   f"tokens {tokens} (every request its full count)")
    log("serving", f"engine trace_count={traces}; first tokens agree with "
                   f"the runner's prefill argmax {agree}/{n} ({clear} with a "
                   f"clear margin, all of those agree)")
    log("serving", f"smoke time {res['wall_seconds']:.1f}s over "
                   f"{res['ticks']} ticks (compile included)")
    log("serving", memory_line(jax.devices()[:1]))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def parity_1f1b(full_cfg, seed: int, *, layers: int = 8,
                bounds=(2, 3, 6, 8), rows: int = 4, seq: int = 256,
                n_micro: int = 4) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.api import (PipelineConfig, init_params, make_stage_mesh,
                           pipeline_step_fn)
    from repro.models.model import loss_fn

    cfg = replace(full_cfg, num_layers=layers)
    mesh = make_stage_mesh(len(bounds))
    log("1f1b", f"{cfg.name} widths, {layers} layers, split {bounds} on "
                f"{len(bounds)} devices, batch {rows}x{seq}, {n_micro} "
                f"microbatches, f32 under 'highest' matmul precision")
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, seq)),
                         jnp.int32)
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        def ref(p):
            return loss_fn(p, {"tokens": tokens, "labels": labels}, cfg,
                           compute_dtype=jnp.float32)
        (_, (l_ref, _)), g_ref = jax.jit(
            jax.value_and_grad(ref, has_aux=True))(params)
        l_ref = float(l_ref)
        g_ref = jax.device_get(g_ref)  # free device 0 for the pipeline
        # one replicated copy per stage device, not a second one on dev 0
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        step = jax.jit(pipeline_step_fn(
            cfg, mesh, bounds, n_micro,
            pipe=PipelineConfig(compute_dtype="float32")))
        l_pipe, g_pipe = step(params, tokens, labels)
        l_pipe = float(l_pipe)
    smoke_s = time.perf_counter() - t0
    worst = 0.0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_ref)[0],
                            jax.tree.leaves(g_pipe)):
        b = np.asarray(b)
        scale = max(float(np.abs(a).max()), 1e-30)
        err = float(np.abs(b - a).max()) / scale
        worst = max(worst, err)
        if err > PARITY_GRAD_TOL:
            raise AssertionError(f"grad {jax.tree_util.keystr(path)}: max "
                                 f"error {err:.3g} of the leaf scale")
    loss_err = abs(l_pipe - l_ref) / abs(l_ref)
    if loss_err > PARITY_LOSS_RTOL:
        raise AssertionError(f"1F1B loss {l_pipe} vs reference {l_ref}")
    log("1f1b", f"loss {l_pipe:.7f} vs value_and_grad(models.loss_fn) "
                f"{l_ref:.7f}: rel err {loss_err:.3g} (tol "
                f"{PARITY_LOSS_RTOL})")
    log("1f1b", f"grads: worst leaf max-error {worst:.3g} of its scale "
                f"(tol {PARITY_GRAD_TOL}) over "
                f"{len(jax.tree.leaves(g_ref))} leaves")
    log("1f1b", f"smoke time {smoke_s:.1f}s (compile included)")
    log("1f1b", memory_line(mesh.devices.tolist()))


def parity_serving(serve_cfg, seed: int, *, boundaries=(9, 18, 27, 36),
                   n_requests: int = 16) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.api import make_stage_mesh
    from repro.serving import ServingService, poisson_trace

    mesh = make_stage_mesh(len(boundaries))
    params = serve_cfg.init_params()
    single = ServingService(serve_cfg, params=params)
    vocab = single.model_cfg.vocab_size
    trace = poisson_trace(n_requests=n_requests, rate_per_sec=8.0,
                          vocab_size=vocab,
                          plen_range=(4, serve_cfg.prompt_pad),
                          gen_range=(4, serve_cfg.max_new), seed=seed)
    cache_len = serve_cfg.prompt_pad + serve_cfg.max_new
    t0 = time.perf_counter()
    res_single = single.run(trace)
    check_completions(res_single, trace, vocab)
    ref = last_prefill_logits(single.runner, params, trace,
                              serve_cfg.prompt_pad, cache_len)
    del single
    # every stage device holds the replicated weights; each stage's KV
    # ring is its own (P(stage) on the cache's leading axis)
    params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    piped = ServingService(replace(serve_cfg, boundaries=tuple(boundaries)),
                           params=params, mesh=mesh)
    res_pipe = piped.run(trace)
    tokens = check_completions(res_pipe, trace, vocab)
    traces = len(piped.step.trace_count)
    if traces != 1:
        raise AssertionError(f"pipeline engine step compiled {traces} times")
    got = last_prefill_logits(piped.runner, params, trace,
                              serve_cfg.prompt_pad, cache_len)
    smoke_s = time.perf_counter() - t0
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    if err > PIPE_LOGIT_RTOL * scale:
        raise AssertionError(f"pipeline prefill logits off by {err:.4g} "
                             f"(largest logit {scale:.4g})")
    first = [int(res_pipe["completions"][r.rid][0]) for r in trace]
    agree, clear, n = greedy_check(ref, first, err)
    same_req = sum(np.array_equal(res_pipe["completions"][r.rid],
                                  res_single["completions"][r.rid])
                   for r in trace)
    same_tok = sum(int((np.asarray(res_pipe["completions"][r.rid])
                        == np.asarray(res_single["completions"][r.rid])).sum())
                   for r in trace)
    ring = piped.state.caches["k"]
    for shard in ring.addressable_shards:
        stage = shard.index[0].start or 0
        if shard.device != mesh.devices[stage]:
            raise AssertionError(f"stage {stage} KV ring on {shard.device}, "
                                 f"expected {mesh.devices[stage]}")
    log("serving4", f"{piped.model_cfg.name} {serve_cfg.compute_dtype}, "
                    f"split {tuple(boundaries)} on {len(boundaries)} devices"
                    f" vs SingleDeviceRunner, {n_requests} requests")
    log("serving4", f"both complete {n_requests}/{n_requests} with full "
                    f"counts ({tokens} tokens); pipeline trace_count={traces}")
    log("serving4", f"prefill logits max |diff| {err:.4g} (largest logit "
                    f"{scale:.4g}, tol {PIPE_LOGIT_RTOL} of it)")
    log("serving4", f"greedy agreement: first tokens {agree}/{n} ({clear} "
                    f"with a margin clear of the error, all agree); "
                    f"identical sequences {same_req}/{n_requests}; "
                    f"tokens agreeing position-wise {same_tok}/{tokens}")
    log("serving4", "KV ring stage k on device k: "
                    + ", ".join(f"stage {s.index[0].start or 0}->dev"
                                f"{s.device.id}"
                                for s in ring.addressable_shards))
    log("serving4", f"smoke time {smoke_s:.1f}s (compile included)")
    log("serving4", memory_line(mesh.devices.tolist()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: planner, executor and serving on one chip; "
                         "4: 1F1B and split serving across four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {devices[0].platform} {devices[0].device_kind} x "
          f"{len(devices)}", flush=True)

    from repro.configs import get_config
    from repro.serving import ServeConfig

    full = get_config(ARCH)
    serve_cfg = ServeConfig(reduced=False, compute_dtype="bfloat16",
                            num_slots=8, prompt_pad=128, max_new=32,
                            seed=args.seed)
    if args.chips == 1:
        phase_planner(full, args.seed)
        phase_executor(full, args.seed)
        phase_serving(serve_cfg, args.seed)
    else:
        parity_1f1b(full, args.seed)
        parity_serving(serve_cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
