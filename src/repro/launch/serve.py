"""Config-driven serving launcher: continuous-batching engine or static.

    # continuous service on a Poisson trace, single device
    PYTHONPATH=src python -m repro.launch.serve --mode engine \
        --requests 32 --rate 8.0

    # split serving: 2-stage plan with per-stage KV rings
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
    PYTHONPATH=src python -m repro.launch.serve --mode engine \
        --set boundaries 1,2

    # everything from a reviewable JSON config, CLI keys override
    PYTHONPATH=src python -m repro.launch.serve --config serve.json \
        --set num_slots 16 --set decode_chunk 4

    # the service loop's spans and request times as JSON lines
    PYTHONPATH=src python -m repro.launch.serve --trace-out serve.jsonl

Every engine/scheduler knob is a :class:`repro.serving.ServeConfig`
field: the launcher loads ``--config`` (JSON), applies ``--set key
value`` overrides, and runs. ``--mode static`` runs the same trace
through the static-batch baseline (``generate_static``: batch, wait for
ALL rows, next batch) for an apples-to-apples comparison.

The v0 ``--data-par/--model-par`` mesh flags are gone: serving
parallelism is now the SPLIT PLAN (``boundaries`` -> pipeline stages
with per-stage KV rings), which is the deployment shape the paper
actually optimizes.
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def run_static(cfg, trace, *, warmup: bool = False):
    """Static-batch baseline: admit in arrival order, N at a time, wait
    for the whole batch (every row pays the batch max gen length).

    ``warmup=True`` runs one throwaway batch before the clock starts so
    the reported wall time excludes the generate compile (the benchmark
    comparison point; the engine side warms the same way).
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.serving.batching import make_generate_fn
    from repro.serving.runners import PipelineRunner, SingleDeviceRunner

    model_cfg = cfg.model_config()
    params = cfg.init_params()
    dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.boundaries is None:
        runner = SingleDeviceRunner(model_cfg, compute_dtype=dtype)
    else:
        from repro.core.pipeline import PipelineConfig
        from repro.distribution.mesh import make_stage_mesh

        runner = PipelineRunner(
            model_cfg, make_stage_mesh(len(cfg.boundaries)), cfg.boundaries,
            pipe=PipelineConfig(compute_dtype=cfg.compute_dtype,
                                wire_dtype=cfg.wire_dtype))
    n = cfg.num_slots
    gen = jax.jit(make_generate_fn(runner, max_new=cfg.max_new,
                                   temperature=cfg.temperature))
    base_key = jax.random.PRNGKey(cfg.seed)
    order = sorted(trace, key=lambda r: r.arrival_time)
    if warmup and order:
        caches = runner.init_caches(n, cfg.prompt_pad + cfg.max_new)
        buf, _ = gen(params, caches,
                     jnp.zeros((n, cfg.prompt_pad), jnp.int32),
                     jnp.ones((n,), jnp.int32), jnp.ones((n,), jnp.int32),
                     jnp.full((n,), -1, jnp.int32), base_key)
        jax.block_until_ready(buf)
    t0 = time.perf_counter()
    done = {}
    lats = {}
    num_batches = 0
    for lo in range(0, len(order), n):
        batch = order[lo:lo + n]
        # arrival-aware, same virtual-clock discipline as the engine's
        # service loop: a batch cannot start before its members arrive,
        # and waiting while idle jumps the clock instead of burning wall
        ready_at = max(r.arrival_time for r in batch)
        now = time.perf_counter() - t0
        if now < ready_at:
            t0 -= ready_at - now
        ap = np.zeros((n, cfg.prompt_pad), np.int32)
        al = np.ones((n,), np.int32)
        ag = np.ones((n,), np.int32)
        ar = np.full((n,), -1, np.int32)
        for i, r in enumerate(batch):
            ap[i, :r.plen] = r.prompt
            al[i] = r.plen
            ag[i] = r.gen_target
            ar[i] = r.rid
        caches = runner.init_caches(n, cfg.prompt_pad + cfg.max_new)
        buf, n_gen = gen(params, caches, jnp.asarray(ap), jnp.asarray(al),
                         jnp.asarray(ag), jnp.asarray(ar), base_key)
        jax.block_until_ready(buf)
        num_batches += 1
        now = time.perf_counter() - t0
        buf = np.asarray(buf)
        for i, r in enumerate(batch):
            done[r.rid] = buf[i, :int(n_gen[i])]
            lats[r.rid] = now - r.arrival_time
    wall = time.perf_counter() - t0
    ls = sorted(lats.values())
    pct = lambda q: ls[min(int(q * len(ls)), len(ls) - 1)] if ls else 0.0
    return {
        "completions": done,
        "num_requests": len(done),
        "wall_seconds": wall,
        "requests_per_sec": len(done) / wall if wall else 0.0,
        "tokens_per_sec": sum(len(t) for t in done.values()) / wall
        if wall else 0.0,
        "p50_latency_s": pct(0.50),
        "p99_latency_s": pct(0.99),
        # structural accounting, comparable to the engine's: useful
        # decode-slot-steps over executed ones. Every batch runs the
        # full max_new-length decode scan on all n rows (drained and
        # padded rows included) - that padding is exactly what the
        # continuous engine's slot reuse reclaims.
        "slot_occupancy": sum(len(t) for t in done.values())
        / (num_batches * n * cfg.max_new) if num_batches else 0.0,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default=None, help="ServeConfig JSON file")
    ap.add_argument("--set", nargs=2, action="append", default=[],
                    metavar=("KEY", "VALUE"),
                    help="override a ServeConfig field")
    ap.add_argument("--mode", choices=("engine", "static"), default="engine")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="emit metrics as one JSON line")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="engine mode: record the service loop's spans "
                         "(repro.tracing) and write them to PATH as JSON "
                         "lines")
    args = ap.parse_args(argv)

    from repro.serving import ServeConfig, poisson_trace

    overrides = {k: ServeConfig.parse_override(k, v) for k, v in args.set}
    cfg = ServeConfig.load(args.config, overrides)
    model_cfg = cfg.model_config()
    trace = poisson_trace(
        n_requests=args.requests, rate_per_sec=args.rate,
        vocab_size=model_cfg.vocab_size,
        plen_range=(4, cfg.prompt_pad), gen_range=(4, cfg.max_new),
        seed=args.trace_seed)

    if args.mode == "static":
        res = run_static(cfg, trace)
    else:
        from repro.serving import ServingService
        from repro.tracing import NULL_TRACER, Tracer

        tracer = Tracer() if args.trace_out else NULL_TRACER
        with tracer:
            svc = ServingService(cfg, tracer=tracer)
            res = svc.run(trace)
        if args.trace_out:
            tracer.dump(args.trace_out)

    metrics = {k: v for k, v in res.items()
               if k not in ("completions", "latencies", "replans")}
    if args.json:
        print(json.dumps(metrics, default=float))
    else:
        print(f"{args.mode}: {res['num_requests']} requests in "
              f"{res['wall_seconds']:.2f}s")
        print(f"  requests/sec {res['requests_per_sec']:.2f}  "
              f"tokens/sec {res['tokens_per_sec']:.1f}")
        print(f"  p50 {res['p50_latency_s']*1e3:.0f} ms  "
              f"p99 {res['p99_latency_s']*1e3:.0f} ms  "
              f"slot occupancy {res['slot_occupancy']:.2f}")
        if "ttft_p50_s" in res:
            print(f"  ttft p50 {res['ttft_p50_s']*1e3:.0f} ms  "
                  f"p95 {res['ttft_p95_s']*1e3:.0f} ms  "
                  f"queue wait p95 {res['queue_wait_p95_s']*1e3:.0f} ms")
    return res


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
