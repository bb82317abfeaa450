"""Model backends for the serving engine.

A runner exposes exactly two pure functions the engine composes into its
jitted step:

* ``prefill(params, caches, prompts, last=None)``: ``prompts`` (B, L)
  int32 -> ``(logits, new_caches)`` - a fresh-sequence pass (scalar
  cache index 0). Without ``last`` the logits are (B, L, V); with
  ``last`` (B,) int32 positions only those rows reach the head and the
  logits are (B, V). The engine prefills its arrival rows this way into
  a scratch cache (:func:`prefill_rows`) and writes each taken row into
  its slot (:func:`write_rows`).
* ``decode(params, tok, caches, pos)``: ``tok`` (B, 1), ``pos`` (B,)
  per-slot entry counts -> ``(logits (B, V), new_caches)`` - one token
  per slot at each slot's OWN position (slot-indexed KV writes, see
  ``models.layers.kv_write``).

Both runners' caches put the slot axis at -4 and the position axis at
-3 of every leaf (``(R, B, C, KH, hd)`` stacks on one device,
``(S, max_len, B, C, KH, hd)`` rings on a stage mesh); the scratch cache
and the row writes rely on those two axes alone.

Both backends restrict to attention-only architectures, MoE included:
padded batched prefill relies on causal masking to keep pad garbage out
of valid rows, which holds for KV caches but NOT for SSM recurrent state
(pad tokens would pollute it). Dropless MoE dispatch computes every
routed token, so each row's output is independent of the rows routed
with it.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ModelConfig
from repro.models import model as M


def check_servable(cfg: ModelConfig) -> None:
    """Raise for architectures the serving engine cannot run correctly.

    Attention configs serve (any layer-group period - the single-device
    runner scans slots natively and the pipeline runner dispatches a
    static block-kind schedule), MoE layers included. SSM/hybrid stay
    rejected: padded batched prefill relies on causal masking, which
    protects KV attention but not recurrent state.
    """
    sig = M.signature(cfg)
    if any(kind != "A" for kind, _, _ in sig):
        raise ValueError(
            "serving engine: SSM/hybrid archs are unservable - padded "
            "batched prefill is masked out of KV attention but would "
            "pollute the recurrent scan state")


class SingleDeviceRunner:
    """Whole model on one device; caches are the stacked per-layer rings."""

    def __init__(self, cfg: ModelConfig, *, compute_dtype=jnp.float32):
        check_servable(cfg)
        self.cfg = cfg
        self.compute_dtype = compute_dtype

    def init_caches(self, num_slots: int, cache_len: int):
        return M.init_caches(self.cfg, num_slots, cache_len,
                             dtype=self.compute_dtype)

    def prefill(self, params, caches, prompts, last=None):
        logits, new_caches, _ = M.forward(
            params, prompts, self.cfg, caches=caches,
            cache_index=jnp.zeros((), jnp.int32), remat=False,
            compute_dtype=self.compute_dtype, last=last)
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

    def prefill(self, params, caches, prompts, last=None):
        return self._prefill(params, caches, prompts, last)

    def decode(self, params, tok, caches, pos):
        return self._decode(params, tok, caches, pos)


def prefill_lengths(prompt_pad: int) -> tuple:
    """The admission prefill's length classes: ``prompt_pad`` / 8, / 4,
    / 2 and ``prompt_pad``, rounded up, duplicates dropped."""
    return tuple(sorted({-(-prompt_pad // d) for d in (8, 4, 2, 1)}))


def prefill_class(prompt_pad: int, longest):
    """Index into ``prefill_lengths(prompt_pad)`` of the smallest class
    that holds a prompt of ``longest`` tokens (a host int, or a traced
    scalar for ``lax.switch``)."""
    return sum(longest > n for n in prefill_lengths(prompt_pad)[:-1])


def prefill_rows(runner, params, caches, prompts, plens, pad_to=None):
    """Prefill ``prompts`` (A, L) as fresh sequences on a zero scratch
    cache shaped like ``caches`` (read for its shapes and dtypes only)
    but with A slots and L positions: at cache index 0 the pass writes all L positions before
    it reads any. Returns ``(logits (A, V) at each row's position
    plens - 1, scratch)``, the scratch zero-padded to ``pad_to``
    positions if given (so that every length class returns one shape)."""
    a, l = prompts.shape
    scratch = jax.tree.map(
        lambda c: jnp.zeros(c.shape[:-4] + (a, l) + c.shape[-2:], c.dtype),
        caches)
    logits, fresh = runner.prefill(params, scratch, prompts, last=plens - 1)
    if pad_to is not None and pad_to > l:
        fresh = jax.tree.map(
            lambda f: jnp.pad(f, [(0, 0)] * (f.ndim - 3)
                              + [(0, pad_to - l), (0, 0), (0, 0)]), fresh)
    # row-major, as the layer loop carries the caches: left to the TPU
    # compiler, the scratch leaves the engine's length-class switch with
    # positions outside the heads, and the row writes then relayout the
    # whole cache (twice for qwen2.5-3b's, in a described-v5e compile)
    return logits, jax.tree.map(
        lambda f: with_layout_constraint(
            f, Layout(major_to_minor=tuple(range(f.ndim)))), fresh)


def write_rows(caches, fresh, slots, taken):
    """Write row ``i`` of the prefill scratch ``fresh`` (all its
    positions) into slot ``slots[i]`` wherever ``taken[i]``: one
    ``dynamic_update_slice`` a row; a row not taken writes back what its
    slot held. A slot's positions past the scratch's keep their values:
    finite, and masked until decode overwrites them."""

    def write(c, f):
        lead = (0,) * (c.ndim - 4)
        size = f.shape[:-4] + (1,) + f.shape[-3:]
        for i in range(f.shape[-4]):
            at = lead + (slots[i], 0, 0, 0)
            row = jax.lax.slice_in_dim(f, i, i + 1, axis=f.ndim - 4)
            old = jax.lax.dynamic_slice(c, at, size)
            c = jax.lax.dynamic_update_slice(
                c, jnp.where(taken[i], row, old), at)
        return c

    return jax.tree.map(write, caches, fresh)
