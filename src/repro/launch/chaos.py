"""Kill-and-resume chaos harness for the training checkpoint path.

The claim under test is the strongest form of crash safety the trainers
promise: a run that is SIGKILLed mid-chunk (no cleanup, no atexit, torn
nothing thanks to the atomic checkpoint writes) and then re-launched
into the same checkpoint directory finishes with metric trajectories
BIT-IDENTICAL to a run that was never interrupted. The harness:

1. launches ``python -m repro.launch.chaos --child ...`` - a subprocess
   running ``train_sac`` with checkpointing, printing ``METRICS {json}``
   on completion;
2. polls the checkpoint directory until a resumable step lands
   (``latest_checkpoint_step``), then delivers ``SIGKILL`` - by
   construction the child dies between chunk boundaries, exactly where
   a real preemption would land;
3. re-launches the SAME command; the child restores the checkpoint
   (``resume=True``) and trains the remaining episodes;
4. compares the resumed metrics against an uninterrupted reference run,
   element-for-element (floats compared by equality, not tolerance). The
   reference is a child too (no ``--dir``: no checkpoints, no kill).

The parent never imports JAX: on an accelerator a chip belongs to one
process, and the parent holding it would starve every child.

``--seeds`` runs the whole dance once per seed (the CI chaos-smoke
matrix). Exit code 0 = every seed bit-identical.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional


def _child_main(args) -> None:
    """Subprocess body: one train_sac run, metrics to stdout. Checkpoints
    under ``--dir`` when given; without it, the uninterrupted reference."""
    from repro.core.agents.loops import train_sac
    from repro.core.agents.sac import SACConfig
    from repro.core.env import MHSLEnv
    from repro.core.profiles import resnet101_profile

    env = MHSLEnv(profile=resnet101_profile(batch=1))
    res = train_sac(
        env, SACConfig(), episodes=args.episodes, seed=args.seed,
        warmup_episodes=args.warmup, num_envs=args.num_envs,
        checkpoint_dir=args.dir, checkpoint_every=args.checkpoint_every)
    print("METRICS " + json.dumps({
        "episode_reward": res.episode_reward,
        "episode_leak": res.episode_leak,
        "episode_violation": res.episode_violation,
        "states_explored": res.states_explored,
    }), flush=True)


def _child_cmd(args, ckpt_dir: Optional[str]) -> List[str]:
    cmd = [
        sys.executable, "-m", "repro.launch.chaos", "--child",
        "--seed", str(args.seed),
        "--episodes", str(args.episodes), "--warmup", str(args.warmup),
        "--num-envs", str(args.num_envs),
        "--checkpoint-every", str(args.checkpoint_every),
    ]
    if ckpt_dir is not None:
        cmd += ["--dir", ckpt_dir]
    return cmd


def _parse_metrics(stdout: str) -> dict:
    for line in stdout.splitlines():
        if line.startswith("METRICS "):
            return json.loads(line[len("METRICS "):])
    raise RuntimeError(f"no METRICS line in child output:\n{stdout}")


def _launch(cmd: List[str]) -> subprocess.Popen:
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def kill_and_resume(args, ckpt_dir: str) -> dict:
    """One chaos round: launch, SIGKILL after the first resumable
    checkpoint, relaunch to completion. Returns the resumed metrics."""
    from repro.checkpoint.train_state import latest_checkpoint_step

    cmd = _child_cmd(args, ckpt_dir)
    victim = _launch(cmd)
    deadline = time.monotonic() + args.timeout
    killed = False
    try:
        while time.monotonic() < deadline:
            step = latest_checkpoint_step(ckpt_dir)
            if step is not None and step >= args.kill_after:
                victim.send_signal(signal.SIGKILL)
                killed = True
                break
            if victim.poll() is not None:
                break  # finished before we could kill it - still valid
            time.sleep(0.05)
        else:
            victim.kill()
            out = victim.communicate()[0]
            raise TimeoutError(
                f"no checkpoint >= {args.kill_after} within "
                f"{args.timeout}s; child output:\n{out}")
        out = victim.communicate()[0]
    finally:
        if victim.poll() is None:
            victim.kill()
            victim.communicate()
    if not killed:
        print(f"  [warn] child finished before the kill landed "
              f"(checkpoint cadence too coarse?); resume still exercised",
              flush=True)
    survivor = _launch(cmd)
    out, _ = survivor.communicate(timeout=args.timeout)
    if survivor.returncode != 0:
        raise RuntimeError(
            f"resume run exited {survivor.returncode}:\n{out}")
    return _parse_metrics(out)


def reference_metrics(args) -> dict:
    """The uninterrupted run, in a child (same code path, no checkpoints,
    no faults)."""
    ref = _launch(_child_cmd(args, None))
    out, _ = ref.communicate(timeout=args.timeout)
    if ref.returncode != 0:
        raise RuntimeError(f"reference run exited {ref.returncode}:\n{out}")
    return _parse_metrics(out)


def compare(resumed: dict, reference: dict) -> List[str]:
    """Bit-exact comparison; returns human-readable mismatches."""
    problems = []
    for k in sorted(set(resumed) | set(reference)):
        a, b = resumed.get(k), reference.get(k)
        if a != b:
            problems.append(f"{k}: resumed {a} != reference {b}")
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true",
                    help="internal: run the training child process")
    ap.add_argument("--dir", default=None,
                    help="checkpoint directory (child; omit for the "
                         "uninterrupted reference) / scratch root")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seeds", default=None,
                    help="comma-separated seed matrix (overrides --seed)")
    ap.add_argument("--episodes", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--num-envs", type=int, default=2)
    ap.add_argument("--checkpoint-every", type=int, default=2)
    ap.add_argument("--kill-after", type=int, default=2,
                    help="SIGKILL once a checkpoint at >= this episode "
                         "exists")
    ap.add_argument("--timeout", type=float, default=600.0)
    args = ap.parse_args(argv)

    if args.child:
        _child_main(args)
        return 0

    import tempfile

    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    failures = 0
    for seed in seeds:
        args.seed = seed
        with tempfile.TemporaryDirectory(dir=args.dir) as root:
            ckpt_dir = os.path.join(root, f"chaos_seed{seed}")
            print(f"[chaos] seed {seed}: kill-and-resume ...", flush=True)
            resumed = kill_and_resume(args, ckpt_dir)
            print(f"[chaos] seed {seed}: uninterrupted reference ...",
                  flush=True)
            ref = reference_metrics(args)
            problems = compare(resumed, ref)
            if problems:
                failures += 1
                print(f"[chaos] seed {seed}: MISMATCH", flush=True)
                for p in problems:
                    print("  " + p, flush=True)
            else:
                n = len(ref["episode_reward"])
                print(f"[chaos] seed {seed}: OK - {n} episode metrics "
                      f"bit-identical after SIGKILL + resume", flush=True)
    if failures:
        print(f"[chaos] {failures}/{len(seeds)} seeds FAILED", flush=True)
        return 1
    print(f"[chaos] all {len(seeds)} seed(s) bit-identical", flush=True)
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
