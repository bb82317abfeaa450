"""Mesh builders over the devices a host has.

The split executor's stage meshes, the RL engine's population mesh and a
small data x model mesh for the GSPMD train step. Each is a FUNCTION, so
importing this module never touches jax device state.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (GSPMD-partitioned)."""
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=types)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices the host actually has (tests)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _make_mesh((data, model), ("data", "model"))


def make_stage_mesh(n_stages: int, stage_axis: str = "stage"):
    """1-D mesh over host devices for the split executor's pipeline stages.

    Stage k of a ``SplitPlan`` runs on device k; ``ppermute`` hops along
    this axis play the paper's wireless activation/gradient hops. Builds
    ``Mesh`` directly from an explicit device slice (``jax.make_mesh``
    picks devices itself, and the stage order must be pinned), so it does
    NOT go through ``_make_mesh``.
    """
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    assert len(devs) >= n_stages, f"need {n_stages} devices, have {len(devs)}"
    return Mesh(np.array(devs[:n_stages]), (stage_axis,))


def make_stage_env_mesh(n_stages: int, n_envs: int | None = None,
                        stage_axis: str = "stage", env_axis: str = "env"):
    """2-D (stage x env) mesh: pipelined stage compute per scenario shard.

    Row s, column e holds stage ``s`` of the split model for env shard
    ``e``: the split executor ppermutes activations along ``stage_axis``
    (hops pinned to device order, like :func:`make_stage_mesh`) while the
    population/data axis shards microbatch rows or scenario sweeps along
    ``env_axis`` - ``distribution.sharding.population_axes`` picks the
    ``'env'`` axis by NAME, so ``train_population`` drives this mesh
    unchanged. ``n_envs=None`` takes every remaining device
    (``len(devices) // n_stages``).
    """
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_envs is None:
        n_envs = len(devs) // n_stages
    need = n_stages * n_envs
    assert n_envs >= 1 and len(devs) >= need, \
        f"need {n_stages}x{n_envs} devices, have {len(devs)}"
    grid = np.array(devs[:need]).reshape(n_stages, n_envs)
    return Mesh(grid, (stage_axis, env_axis))


def make_population_mesh(num_devices: int | None = None, axis: str = "env"):
    """1-D mesh over host devices for the RL engine's population axis.

    The vectorized trainers shard the ``num_envs`` / scenario axis of their
    env states and replay buffers over this mesh (agent params stay
    replicated); a 1-device mesh is the bit-identical fallback to the plain
    vmap path. ``num_devices=None`` takes every device the host has.
    """
    devs = jax.devices()
    n = len(devs) if num_devices is None else num_devices
    assert n <= len(devs), (n, len(devs))
    return _make_mesh((n,), (axis,))
