"""Model backends for the serving engine.

A runner exposes exactly two pure functions the engine composes into its
jitted step:

* ``prefill(params, caches, prompts)``: ``prompts`` (B, P) int32 ->
  ``(logits (B, P, V), new_caches)`` - a fresh-sequence pass (scalar
  cache index 0). The engine gathers each row's logits at its own
  prompt length and WHERE-merges the cache rows of the slots it admitted.
* ``decode(params, tok, caches, pos)``: ``tok`` (B, 1), ``pos`` (B,)
  per-slot entry counts -> ``(logits (B, V), new_caches)`` - one token
  per slot at each slot's OWN position (slot-indexed KV writes, see
  ``models.layers.kv_write``).

Both backends restrict to attention-only architectures (MoE allowed
under DROPLESS dispatch): padded batched prefill relies on causal
masking to keep pad garbage out of valid rows, which holds for KV caches
but NOT for SSM recurrent state (pad tokens would pollute it) or
capacity-bounded MoE routing (pad tokens would steal expert capacity
from real rows - dropless dispatch computes every routed token, so each
row's output is independent of its dispatch-group neighbours).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M

Array = jax.Array


def check_servable(cfg: ModelConfig) -> None:
    """Raise for architectures the serving engine cannot run correctly.

    Attention configs serve (any layer-group period - the single-device
    runner scans slots natively and the pipeline runner dispatches a
    static block-kind schedule); MoE layers serve under DROPLESS dispatch
    only. SSM/hybrid stay rejected: padded batched prefill relies on
    causal masking, which protects KV attention but not recurrent state.
    """
    sig = M.signature(cfg)
    if any(kind != "A" for kind, _, _ in sig):
        raise ValueError(
            "serving engine: SSM/hybrid archs are unservable - padded "
            "batched prefill is masked out of KV attention but would "
            "pollute the recurrent scan state")
    if any(is_moe for _, is_moe, _ in sig) and cfg.moe.dispatch != "dropless":
        raise ValueError(
            "serving engine: capacity-dropping MoE is unservable (padded "
            "prefill rows steal expert capacity from real rows); set "
            "moe.dispatch='dropless'")


class SingleDeviceRunner:
    """Whole model on one device; caches are the stacked per-layer rings."""

    def __init__(self, cfg: ModelConfig, *, compute_dtype=jnp.float32):
        check_servable(cfg)
        self.cfg = cfg
        self.compute_dtype = compute_dtype

    def init_caches(self, num_slots: int, cache_len: int):
        return M.init_caches(self.cfg, num_slots, cache_len,
                             dtype=self.compute_dtype)

    def prefill(self, params, caches, prompts):
        logits, new_caches, _ = M.forward(
            params, prompts, self.cfg, caches=caches,
            cache_index=jnp.zeros((), jnp.int32), remat=False,
            compute_dtype=self.compute_dtype)
        return logits, new_caches

    def decode(self, params, tok, caches, pos):
        logits, new_caches, _ = M.forward(
            params, tok, self.cfg, caches=caches, cache_index=pos,
            remat=False, compute_dtype=self.compute_dtype)
        return logits[:, -1], new_caches


class PipelineRunner:
    """Split plan on a stage mesh: per-stage KV rings, ppermute hops.

    ``boundaries`` is the split plan's cumulative cut points (the Eq. 10
    decision variable); each stage holds only its own layers' KV ring and
    activations cross stage boundaries on the wire
    (``PipelineConfig.wire_dtype``) - serving the model exactly as the
    paper deploys it across hops.
    """

    def __init__(self, cfg: ModelConfig, mesh, boundaries: Sequence[int],
                 *, stage_axis: str = "stage", pipe=None):
        from repro.core.pipeline import PipelineConfig, pipeline_serve_fns

        check_servable(cfg)
        if pipe is None:
            pipe = PipelineConfig(compute_dtype="float32")
        self.cfg = cfg
        self.mesh = mesh
        self.boundaries = tuple(int(b) for b in boundaries)
        self.stage_axis = stage_axis
        self.pipe = pipe
        self.compute_dtype = pipe.dtype
        self._prefill, self._decode = pipeline_serve_fns(
            cfg, mesh, self.boundaries, stage_axis=stage_axis, pipe=pipe)

    def init_caches(self, num_slots: int, cache_len: int):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core.pipeline import stage_kv_caches

        caches = stage_kv_caches(self.cfg, self.boundaries, num_slots,
                                 cache_len, dtype=self.compute_dtype)
        # place fresh rings with their steady-state sharding up front:
        # the serve passes emit P(stage)-sharded caches, and feeding the
        # engine step host-layout zeros on call 1 then stage-sharded
        # caches on call 2 would compile the step TWICE (one executable
        # per input sharding - a multi-second hiccup mid-service)
        sharding = NamedSharding(self.mesh, PartitionSpec(self.stage_axis))
        return jax.tree.map(lambda c: jax.device_put(c, sharding), caches)

    def prefill(self, params, caches, prompts):
        return self._prefill(params, caches, prompts)

    def decode(self, params, tok, caches, pos):
        return self._decode(params, tok, caches, pos)


def cache_where(mask: Array, new_caches, old_caches):
    """Per-slot cache select: ``mask`` (B,) picks NEW rows, else old.

    Works for both runner cache layouts - the slot axis is the unique
    axis of size ``B = len(mask)``... which is ambiguous in general, so
    the axis is located by matching ``B`` from the RIGHT (the slot axis
    sits left of (kv_len, KH, hd) in both layouts: axis -4).
    """

    def one(n, o):
        m = mask.reshape((-1,) + (1,) * 3)
        return jnp.where(
            jnp.expand_dims(m, tuple(range(n.ndim - 4))), n, o)

    return jax.tree.map(one, new_caches, old_caches)
