"""Sharding rules: logical roles -> PartitionSpecs on a data x model mesh.

Strategy (MaxText-style FSDP + tensor parallelism):
  * weights: one dim sharded over 'data' (FSDP / ZeRO-3) and one over
    'model' (tensor parallel), chosen per logical role, only when divisible;
  * batch: leading batch dim over ('pod', 'data'); GSPMD propagates the
    activations' shardings from the parameters and the batch.

The 'pod' axis (multi-pod mesh) carries pure data parallelism: weights are
replicated across pods (DCN is too slow for cross-pod FSDP) and gradients
all-reduce over ('pod', 'data').
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _maybe(axis: Optional[str], dim: int, mesh: Mesh):
    """Use `axis` for a dim only if the dim is divisible by the axis size."""
    if axis is None:
        return None
    if axis not in mesh.axis_names:
        return None
    if dim % mesh_axis_size(mesh, axis) != 0:
        return None
    return axis


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_axes(mesh: Mesh, batch: int):
    """Largest prefix of ('pod','data') whose product divides batch."""
    axes = []
    prod = 1
    for a in _data_axes(mesh):
        prod *= mesh_axis_size(mesh, a)
        if batch % prod == 0:
            axes.append(a)
        else:
            break
    return tuple(axes) if axes else None


def batch_sharding(mesh: Mesh, batch_spec, *, extra_dims: int = 1) -> NamedSharding:
    """Sharding for (B, ...) arrays: B over ('pod','data') when divisible."""
    b = batch_spec if isinstance(batch_spec, int) else batch_spec.shape[0]
    return named(mesh, batch_axes(mesh, b), *([None] * extra_dims))


# ---------------------------------------------------------------------------
# population / env-axis sharding (RL engine)
# ---------------------------------------------------------------------------

ENV_AXIS = "env"


def population_axes(mesh: Mesh, num: int):
    """Mesh axes for a population axis of size ``num``.

    A dedicated ``'env'`` axis (``distribution.mesh.make_population_mesh``)
    wins; otherwise the population rides the pure-data-parallel prefix of a
    data x model mesh (``('pod', 'data')``), largest divisible prefix. Returns
    ``None`` (replicate) when nothing divides ``num``.
    """
    if ENV_AXIS in mesh.axis_names:
        return _maybe(ENV_AXIS, num, mesh)
    return batch_axes(mesh, num)


def population_sharding(mesh: Mesh, num: int, ndim: int) -> NamedSharding:
    """Sharding for a ``(num, ...)`` population-axis array of rank ``ndim``:
    leading axis over the population mesh axes, everything else replicated.
    Indivisible populations fall back to full replication."""
    return named(mesh, population_axes(mesh, num), *([None] * (ndim - 1)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement (agent params shared by every shard)."""
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# stage x env sharding (split executor on a 2-D mesh)
# ---------------------------------------------------------------------------

STAGE_AXIS = "stage"


def stage_sharding(mesh: Mesh, ndim: int, stage_axis: str = STAGE_AXIS) -> NamedSharding:
    """Sharding for ``(S, ...)`` stage-stacked arrays (restacked block
    params, per-stage lengths) on a mesh with a ``stage`` axis: leading dim
    over the stage axis, replicated along every other axis (in particular
    along ``env`` on a 2-D stage x env mesh)."""
    ax = stage_axis if stage_axis in mesh.axis_names else None
    return named(mesh, ax, *([None] * (ndim - 1)))


def microbatch_sharding(mesh: Mesh, ndim: int, env_axis: str = ENV_AXIS) -> NamedSharding:
    """Sharding for ``(M, mb, ...)`` microbatched data on a 2-D
    stage x env mesh: microbatch ROWS over the env axis (data parallelism
    composed with the pipeline), the schedule dim and everything trailing
    replicated. On a stage-only mesh this degrades to full replication."""
    ax = env_axis if env_axis in mesh.axis_names else None
    return named(mesh, None, ax, *([None] * (ndim - 2)))


# ---------------------------------------------------------------------------
# parameter sharding by key path
# ---------------------------------------------------------------------------


def spec_for_param(path: str, shape: Tuple[int, ...], cfg: ModelConfig, mesh: Mesh) -> P:
    """Map a parameter (by key path + shape) to a PartitionSpec.

    Stacked layer-group params have a leading `repeats` dim (never sharded).
    """
    dims = list(shape)
    stacked = "slots/" in path
    off = 1 if stacked and len(dims) >= 2 else 0  # leading repeats dim

    def spec(*entries):
        full = [None] * len(dims)
        for i, ax in enumerate(entries):
            full[off + i] = _maybe(ax, dims[off + i], mesh)
        return P(*full)

    leaf = path.split("/")[-1]
    if leaf in ("embed",):  # (V, D)
        return spec("model", "data")
    if leaf == "lm_head":  # (D, V)
        return spec("data", "model")
    if leaf in ("wq", "wk", "wv"):  # (D, H*hd)
        return spec("data", "model")
    if leaf == "wo":  # (H*hd, D)
        return spec("model", "data")
    if leaf in ("bq", "bk", "bv"):
        return spec("model")
    if leaf in ("w_gate", "w_up"):
        if len(dims) - off == 3:  # MoE (E, D, F)
            return spec("model", "data", None)
        return spec("data", "model")  # (D, F)
    if leaf == "w_down":
        if len(dims) - off == 3:  # MoE (E, F, D)
            return spec("model", None, "data")
        return spec("model", "data")  # (F, D)
    if leaf == "router":  # (D, E)
        return spec("data", None)
    if leaf == "in_proj":  # (D, Din)
        return spec("data", "model")
    if leaf == "out_proj":  # (di, D)
        return spec("model", "data")
    if leaf == "proj":  # frontend (d_in, D)
        return spec("data", "model")
    # norms, biases, conv, scalars: replicated
    return P(*([None] * len(dims)))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_shardings(params_shape, cfg: ModelConfig, mesh: Mesh):
    """Tree of NamedShardings matching a params (or opt-state) shape tree:
    FSDP over 'data' + tensor parallel over 'model'."""

    def one(path, leaf):
        return NamedSharding(
            mesh, spec_for_param(_path_str(path), tuple(leaf.shape), cfg, mesh))

    return jax.tree_util.tree_map_with_path(one, params_shape)
