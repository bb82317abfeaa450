"""End-to-end MHSL driver (the paper's full loop):

1. train the ICM-CA SAC controller on the wireless MHSL environment for a
   chosen architecture's layer profile;
2. roll out the learned policy -> a split plan (boundaries + devices);
3. EXECUTE that plan as real pipeline-parallel training of the (reduced)
   model over multiple JAX devices, multi-hop activations via ppermute.

On the CPU, give the pipeline stage [3/3] four host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python examples/train_mhsl_rl.py --arch qwen2.5-3b

On an accelerator the stages take the devices JAX finds (one per stage,
at most ``--stages``).
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
from dataclasses import replace

from repro.api import (MHSLEnv, NetworkConfig, PipelineConfig, SACConfig,
                       adamw, get_config, init_params, make_stage_mesh,
                       pipeline_step_fn, rollout_plan, train_sac,
                       transformer_profile)
from repro.optim.optimizers import apply_updates


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--episodes", type=int, default=60)
    ap.add_argument("--pipeline-steps", type=int, default=20)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--num-envs", type=int, default=4,
                    help="vmapped env population per rollout chunk")
    ap.add_argument("--shard-envs", action="store_true",
                    help="shard the num-envs axis over a population mesh "
                         "spanning every host device")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="save/resume RL training state under this directory")
    ap.add_argument("--checkpoint-every", type=int, default=20,
                    help="episodes between checkpoints (with --checkpoint-dir)")
    ap.add_argument("--fresh", action="store_true",
                    help="ignore an existing checkpoint and train from scratch")
    args = ap.parse_args()

    model_cfg_full = get_config(args.arch)
    # 1) RL controller on the FULL architecture's layer profile
    prof = transformer_profile(model_cfg_full, batch=1, seq=128)
    env = MHSLEnv(profile=prof, net=NetworkConfig(max_split=args.stages))
    sac_cfg = SACConfig()
    mesh = None
    if args.shard_envs:
        from repro.distribution.mesh import make_population_mesh

        mesh = make_population_mesh()
        print(f"      population mesh: {len(jax.devices())} devices, "
              f"num_envs axis sharded")
    print(f"[1/3] training ICM-CA SAC on {args.arch} profile "
          f"({prof.num_layers} layers, {args.episodes} episodes, "
          f"{args.num_envs} vmapped envs)...")
    res = train_sac(env, sac_cfg, episodes=args.episodes, warmup_episodes=10,
                    num_envs=args.num_envs, mesh=mesh,
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every,
                    resume=not args.fresh)
    print(f"      reward: first10={np.mean(res.episode_reward[:10]):.2f} "
          f"last10={np.mean(res.episode_reward[-10:]):.2f}")

    boundaries_full, devices, leaked, t_r, e_r = rollout_plan(env, res.params, sac_cfg)
    print(f"[2/3] learned plan on {prof.num_layers} layers: boundaries={boundaries_full} "
          f"devices={devices} leaked={leaked:.3f} T_R={t_r:.2f}s E_R={e_r:.1f}J")

    # 3) execute the plan (rescaled to the reduced model depth) as a real
    # pipeline across `stages` JAX devices
    n_dev = len(jax.devices())
    stages = min(args.stages, n_dev)
    depth = 8
    cfg = replace(get_config(args.arch).reduced(), num_layers=depth)
    # rescale the learned stage-length fractions to the reduced depth
    lens_full = np.diff(np.concatenate([[0], np.asarray(boundaries_full)]))
    lens = np.maximum(1, np.round(lens_full / lens_full.sum() * depth).astype(int))
    lens = lens[:stages]
    while lens.sum() > depth:
        lens[np.argmax(lens)] -= 1
    while lens.sum() < depth:
        lens[np.argmin(lens)] += 1
    boundaries = tuple(int(b) for b in np.cumsum(lens))
    print(f"[3/3] executing plan {boundaries} as a {stages}-stage pipeline "
          f"on {n_dev} devices")

    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_stage_mesh(stages)
    # the 1F1B executor: interleaved schedule, masked uneven stages
    step_fn = pipeline_step_fn(cfg, mesh, boundaries=boundaries,
                               n_microbatches=2, pipe=PipelineConfig())
    opt = adamw(3e-4, max_grad_norm=1.0)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state, tokens, labels):
        loss, grads = step_fn(params, tokens, labels)
        ups, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, ups), opt_state, loss

    rng = np.random.default_rng(0)
    for step in range(args.pipeline_steps):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)), jnp.int32)
        labs = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)), jnp.int32)
        params, opt_state, loss = train_step(params, opt_state, toks, labs)
        if step % 5 == 0 or step == args.pipeline_steps - 1:
            print(f"      pipeline step {step:3d} loss {float(loss):.4f}")
    print("done: RL-planned multi-hop split training executed as a real pipeline.")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
