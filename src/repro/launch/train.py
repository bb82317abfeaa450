"""Training launcher: the GSPMD train step on a host mesh.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --steps 50 [--reduced] [--data-par 2 --model-par 2]

--reduced is the default. The step runs under jit with its parameter,
optimizer-state and batch shardings on a data x model host mesh; GSPMD
propagates the activations' shardings from those.
"""
import argparse
import os
import time

# host device count must be set before jax import when multi-device CPU
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import save_pytree
from repro.configs import get_config
from repro.data import synthetic_stream
from repro.distribution.mesh import make_host_mesh
from repro.distribution.sharding import batch_axes, param_shardings
from repro.models import init_params, make_train_step
from repro.optim import adamw, linear_warmup_cosine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--data-par", type=int, default=2)
    ap.add_argument("--model-par", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--bf16-compute", action="store_true")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh(args.data_par, args.model_par)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}  model: {cfg.name}")

    params = init_params(jax.random.PRNGKey(0), cfg)
    psh = param_shardings(jax.eval_shape(lambda: params), cfg, mesh)
    params = jax.tree.map(lambda a, s: jax.device_put(a, s), params, psh)
    opt = adamw(linear_warmup_cosine(args.lr, 10, args.steps), max_grad_norm=1.0)
    opt_state = opt.init(params)
    osh = param_shardings(jax.eval_shape(lambda: opt_state), cfg, mesh)

    step_fn = make_train_step(
        cfg, opt, compute_copy_dtype=jnp.bfloat16 if args.bf16_compute else None
    )
    baxes = batch_axes(mesh, args.batch)
    stream = synthetic_stream(cfg, args.batch, args.seq)
    ex = next(stream)
    bsh = {k: NamedSharding(mesh, P(baxes, *([None] * (v.ndim - 1)))) for k, v in ex.items()}
    jitted = jax.jit(step_fn, in_shardings=(psh, osh, bsh), out_shardings=(psh, osh, None))

    t0 = time.time()
    for step in range(args.steps):
        batch = jax.tree.map(jax.device_put, next(stream), bsh)
        params, opt_state, m = jitted(params, opt_state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(m['loss']):.4f}  "
                  f"({time.time() - t0:.1f}s)", flush=True)
    if args.ckpt:
        save_pytree(params, args.ckpt)
        print(f"saved -> {args.ckpt}")


if __name__ == "__main__":
    main()
