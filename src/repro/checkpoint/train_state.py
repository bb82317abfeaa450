"""Stop/resume checkpoints for the RL population trainers.

A training checkpoint is a pair of files per step under one directory:

* ``step_{N:08d}.npz``  - the DEVICE state (agent params, optimizer state,
  replay buffer storage + ring pointers, PRNG keys), written through
  ``checkpoint.store.save_pytree``;
* ``step_{N:08d}.json`` - the HOST state (episode counter, per-episode
  metric curves, the distinct-states-explored hash set) - everything the
  training loop keeps in Python between chunks;

plus a ``LATEST`` file naming the newest step. Both trainers checkpoint at
chunk boundaries, where the loop state above is the COMPLETE state of the
run: restoring it and re-entering the loop replays the exact key
derivations and buffer contents, so a resumed run's episode-reward
trajectory is bit-identical to an uninterrupted one (pinned by
``tests/test_population_mesh.py``).

Restore is sharding-aware: pass ``shardings`` (or a ``like`` tree of
already-placed arrays) and every leaf is ``device_put`` onto its mesh
placement, so long sharded-population runs resume straight onto the mesh.

JAX is imported only by the functions that touch arrays: the chaos
harness's parent polls :func:`latest_checkpoint_step` and must stay off
the accelerator its children use.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
from typing import Any, Dict, Optional, Tuple

import numpy as np

_STEP_RE = re.compile(r"^step_(\d{8})\.npz$")


def pytree_fingerprint(tree: Any) -> Optional[str]:
    """Content hash of a pytree of arrays (order = tree order), used to
    fingerprint the scenario physics a run was trained under. None in,
    None out (no scenario override)."""
    if tree is None:
        return None
    import jax

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def validate_resume(host_state: Dict[str, Any], meta: Dict[str, Any],
                    episodes: int, directory: str) -> int:
    """Shared resume gate for the trainers: the checkpoint's run
    fingerprint must match the caller's knobs exactly, and the saved
    episode counter must not be past the requested run length - resuming
    under different knobs would silently produce a trajectory belonging to
    neither run. Returns the restored episode counter."""
    if host_state.get("meta") != meta:
        raise ValueError(
            f"checkpoint {directory} was written by a run with "
            f"{host_state.get('meta')}, cannot resume with {meta}")
    ep = int(host_state["ep"])
    if ep > episodes:
        raise ValueError(
            f"checkpoint {directory} is at episode {ep}, past the "
            f"requested episodes={episodes}")
    return ep


def _npz_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"step_{step:08d}.npz")


def _json_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"step_{step:08d}.json")


def save_train_checkpoint(directory: str, step: int, device_state: Any,
                          host_state: Dict[str, Any]) -> str:
    """Write one checkpoint; returns the .npz path. ``LATEST`` is updated
    last (atomic rename) so a crash mid-write never corrupts the newest
    resumable step."""
    from repro.checkpoint.store import save_pytree

    os.makedirs(directory, exist_ok=True)
    save_pytree(device_state, _npz_path(directory, step))
    tmp = _json_path(directory, step) + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"step": step, **host_state}, f)
    os.replace(tmp, _json_path(directory, step))
    tmp = os.path.join(directory, "LATEST.tmp")
    with open(tmp, "w") as f:
        f.write(str(step))
    os.replace(tmp, os.path.join(directory, "LATEST"))
    return _npz_path(directory, step)


def _complete(directory: str, step: int) -> bool:
    """Both halves of the checkpoint must exist - a crash between the npz
    and json writes leaves an orphan that must not be offered for resume."""
    return (os.path.exists(_npz_path(directory, step))
            and os.path.exists(_json_path(directory, step)))


def latest_checkpoint_step(directory: str) -> Optional[int]:
    """Newest complete step in ``directory`` (None when empty/missing).
    Trusts ``LATEST`` when present and valid, else scans the step files."""
    if not os.path.isdir(directory):
        return None
    latest = os.path.join(directory, "LATEST")
    if os.path.exists(latest):
        try:
            with open(latest) as f:
                step = int(f.read().strip())
        except (ValueError, OSError):
            step = None  # unreadable/garbage LATEST: fall back to the scan
        if step is not None and _complete(directory, step):
            return step
    steps = [int(m.group(1)) for name in os.listdir(directory)
             if (m := _STEP_RE.match(name)) and _complete(directory,
                                                          int(m.group(1)))]
    return max(steps) if steps else None


def load_train_checkpoint(
    directory: str, like: Any, *, step: Optional[int] = None,
    shardings: Optional[Any] = None,
) -> Tuple[int, Any, Dict[str, Any]]:
    """Restore ``(step, device_state, host_state)``.

    ``like`` is the freshly-initialized device-state pytree (structure,
    shapes, dtypes - and, when already placed on a mesh, the shardings to
    restore onto unless ``shardings`` overrides them).
    """
    import jax

    from repro.checkpoint.store import load_pytree

    if step is None:
        step = latest_checkpoint_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {directory}")
    if shardings is None:
        leaves = jax.tree.leaves(like)
        if leaves and all(hasattr(x, "sharding") for x in leaves):
            shardings = jax.tree.map(lambda x: x.sharding, like)
    device_state = load_pytree(_npz_path(directory, step), like,
                               shardings=shardings)
    with open(_json_path(directory, step)) as f:
        host_state = json.load(f)
    return step, device_state, host_state
