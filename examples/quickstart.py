"""Quickstart: train a ~100M-param LM for a few hundred steps on CPU.

    PYTHONPATH=src python examples/quickstart.py [--arch stablelm-1.6b]
                                                 [--steps 300] [--d-model 512]

Uses the public API only: config -> reduced-but-real model -> synthetic
data pipeline -> AdamW train loop -> checkpoint save/restore.
"""
import argparse
import time
from dataclasses import replace

import jax
import jax.numpy as jnp

from repro.api import (adamw, get_config, init_params, linear_warmup_cosine,
                       load_pytree, make_train_step, save_pytree,
                       synthetic_stream)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/quickstart_ckpt.npz")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    cfg = replace(cfg, num_layers=args.layers, d_model=args.d_model,
                  num_heads=max(cfg.num_heads, 4) or 4,
                  num_kv_heads=max(cfg.num_kv_heads, 2) or 2,
                  head_dim=64, vocab_size=2048, name=f"{args.arch}-quickstart")
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name}  params={n_params/1e6:.1f}M  layers={cfg.num_layers}")

    opt = adamw(linear_warmup_cosine(3e-4, warmup=20, total_steps=args.steps),
                weight_decay=0.01, max_grad_norm=1.0)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt, remat=False))

    stream = synthetic_stream(cfg, args.batch, args.seq)
    t0 = time.time()
    for step in range(args.steps):
        batch = next(stream)
        params, opt_state, m = step_fn(params, opt_state, batch)
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(m['loss']):.4f}  "
                  f"({(time.time()-t0):.1f}s)")
    save_pytree(params, args.ckpt)
    restored = load_pytree(args.ckpt, jax.eval_shape(lambda: params))
    assert all(
        bool(jnp.allclose(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored))
    )
    print(f"checkpoint round-trip ok -> {args.ckpt}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
