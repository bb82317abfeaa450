"""Batched serving example: ONE fused generate dispatch.

    PYTHONPATH=src python examples/serve.py --arch qwen2_5_3b \
        --batch 4 --prompt-len 64 --gen 32

Thin wrapper over :func:`repro.serving.batching.generate_static` - the
shared static-generate core (padded batched prefill + a single jitted
``lax.scan`` over the decode steps, so the whole generation is one
device dispatch instead of the v0 per-token host loop). The continuous
service with request arrivals lives in ``repro.launch.serve``.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import init_params
from repro.serving import SingleDeviceRunner, generate_static
from repro.serving.config import ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_5_3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = ServeConfig(arch=args.arch.replace("-", "_").replace(".", "_"))
    model_cfg = cfg.model_config()
    params = init_params(jax.random.PRNGKey(0), model_cfg)
    runner = SingleDeviceRunner(model_cfg)

    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, model_cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)
    plens = np.full((args.batch,), args.prompt_len, np.int32)
    gens = np.full((args.batch,), args.gen, np.int32)

    t0 = time.time()
    toks, n_gen = generate_static(
        runner, params, prompts, plens, gens, max_new=args.gen,
        temperature=args.temperature)
    jax.block_until_ready(toks)
    dt = time.time() - t0
    total = int(np.asarray(n_gen).sum())
    print(f"generate: {args.batch}x{args.prompt_len}+{args.gen} in "
          f"{dt*1e3:.1f} ms ({total/max(dt,1e-9):.0f} tok/s incl. compile, "
          "one dispatch)")
    gen = np.asarray(toks)
    print("sample generations (token ids):")
    for b in range(min(args.batch, 2)):
        print(f"  [{b}] {gen[b][:16].tolist()} ...")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
