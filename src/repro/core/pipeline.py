"""TPU-native MHSL executor: a split plan runs as pipeline parallelism.

The paper's multi-hop split learning IS pipeline parallelism: sub-model k
on device s_k, activations hop s_k -> s_{k+1} (Eq. 1), gradients hop back
(Eq. 4). Here a ``SplitPlan`` executes on a TPU mesh 'stage' axis via
``shard_map`` with ``jax.lax.ppermute`` hops - ICI links play the role of
the wireless links.

Two schedules, selected by :class:`PipelineConfig`:

* ``fill_drain`` (the reference): a GPipe-style forward scan of
  ``M + S - 1`` ticks whose backward comes from ``jax.grad`` reversing
  the scan (all forwards, then all backwards). Every stage is padded to
  the longest stage with zero-initialized blocks - exact identities, so
  the function is preserved, but the padded blocks and the per-tick
  final-norm + LM-head + loss computed on EVERY stage all burn real
  compute.
* ``1f1b`` (the fast path, :func:`pipeline_step_fn`): an interleaved
  one-forward-one-backward schedule over ``M + 2(S-1)`` ticks. Each tick
  a stage runs the forward of one in-flight microbatch AND the manual
  VJP of another (warmup/drain slots are ``lax.cond``-ed out, so idle
  ticks skip their compute); activations/cotangents hop between ticks as
  donated scan carries via paired ``ppermute``s. Stage compute is masked
  to the stage's ACTIVE length (a per-stage ``lax.cond`` over the padded
  block scan), so uneven RL splits no longer pay the padded max-length
  matmuls - the Eq. 10 imbalance cost stays visible as bubble ticks, not
  as fake FLOPs. The LM head/loss runs only on the last stage's backward
  slot, and its param gradients accumulate in fp32 on-device, sharded by
  stage. Backward slots rematerialize their stage forward from a stashed
  stage input (depth ``2(S-1)+1`` ring), which is what bounds the stash
  at O(S) activations instead of GPipe's O(M).

Stage handoffs are DOUBLE-BUFFERED by default
(``PipelineConfig.transport="overlap"``): the scan carry holds the
wire-dtype SEND buffers produced by the previous tick, and both
``ppermute`` hops are issued at the top of the tick - before any of the
tick's block compute - so XLA's async collectives
(``collective-permute-start``/``-done``) can overlap each hop with the
slot that does not consume it (the forward hop hides behind the backward
VJP and vice versa). ``transport="sync"`` keeps the PR-5 barrier shape
(hops issued after the tick's compute, on its fresh outputs) as the
measured baseline; both transports consume every buffer on the same tick,
so they are numerically identical. Activations/cotangents are cast to
``PipelineConfig.wire_dtype`` before the hop (default: the compute
dtype), so the wire pays bf16 bytes even when stages accumulate in fp32 -
the paper's Eq. 1/4 transmissions priced per
``repro.core.transport``'s link model.

A 2-D (stage x env) mesh (``distribution.mesh.make_stage_env_mesh``) composes
this pipeline with data parallelism: pass ``env_axis`` and the
microbatch-row dim of ``tokens``/``labels`` shards over ``env`` while
stage params replicate across it; loss and grads are ``pmean``-ed over
the env axis after the stage ``psum``.

Uneven splits (the RL agent's choice!) are supported by padding every
stage to the longest stage with zero-initialized blocks: residual blocks
with zeroed projections are exact identities, so the pipeline computes the
same function while exposing the real cost of imbalance - exactly the
trade-off the paper's Eq. 10 penalizes.

Mixed block types (the model-zoo case: Jamba's A/M hybrid period, MoE
every-k layers) run through the 1F1B schedule via a UNION param layout:
every layer row carries every field any signature in the layer-group
period uses (attn, mamba, mlp, moe), zero-filled where foreign, and a
STATIC per-slot block-kind schedule (one int8 code per layer, restacked
per stage like the params) drives a ``lax.switch`` inside the stage scan
- one branch per distinct signature, each reading only its own fields,
so the foreign zero rows get exact-zero gradients. Homogeneous
(period-1) architectures keep the original single-signature fast path
with no switch and no union padding; the fill-drain reference remains
period-1 only (mixed parity is pinned against the plain ``M.forward``
loss instead, see tests/test_pipeline_schedule.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distribution.mesh import make_stage_mesh  # noqa: F401  (re-export)
from repro.models import model as M
from repro.models import layers as L


@dataclass(frozen=True)
class PipelineConfig:
    """Split-executor knobs.

    ``schedule``: ``"1f1b"`` (interleaved steady-state, masked uneven
    splits, manual per-stage VJP) or ``"fill_drain"`` (the GPipe-style
    reference whose backward is ``jax.grad`` of the forward scan).
    ``stage_impl``: ``"reference"`` applies blocks through
    ``models.layers``; ``"pallas"`` routes the residual MLP half-block
    through the fused Pallas stage kernel
    (``repro.kernels.stage_block``, interpret-mode on CPU).
    ``transport``: ``"overlap"`` (double-buffered handoff, hops issued at
    the top of the tick on the previous tick's send buffers) or ``"sync"``
    (hops issued after the tick's compute - the PR-5 barrier baseline).
    ``wire_dtype``: dtype activations/cotangents are cast to before each
    ``ppermute`` hop; ``None`` keeps them in ``compute_dtype`` (no cast,
    bit-identical to the seed executor).
    """

    schedule: str = "1f1b"
    stage_impl: str = "reference"
    # activation dtype in stage compute. bf16 is the production default;
    # the grad-parity tests pin both schedules at f32, where reassociation
    # noise drops below the 2e-5 gate.
    compute_dtype: str = "bfloat16"
    # activation/cotangent dtype ON THE WIRE (the ppermute payload).
    # None -> compute_dtype. Setting e.g. "bfloat16" under fp32 compute
    # halves Eq. 1/4 hop bytes at a quantization cost the parity tests
    # bound.
    wire_dtype: Optional[str] = None
    transport: str = "overlap"

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def wire(self):
        return jnp.dtype(self.wire_dtype or self.compute_dtype)

    @property
    def block_impl(self) -> str:
        assert self.stage_impl in ("reference", "pallas"), self.stage_impl
        return "pallas_stage" if self.stage_impl == "pallas" else "auto"

    def __post_init__(self):
        if self.transport not in ("overlap", "sync"):
            raise ValueError(
                f"transport must be 'overlap' or 'sync', got {self.transport!r}")


def _check_boundaries(boundaries: Sequence[int],
                      num_layers: Optional[int] = None) -> None:
    """Validate split-plan cut points before they reach the executor.

    ``boundaries`` are CUMULATIVE layer counts: strictly increasing,
    positive, and (when the layer count is known) ending exactly at
    ``num_layers``. A malformed plan would otherwise produce silently
    empty or overlapping stages deep inside ``shard_map``.
    """
    bl = list(boundaries)
    if not bl:
        raise ValueError("boundaries must be non-empty")
    lo = 0
    for k, b in enumerate(bl):
        if int(b) <= lo:
            raise ValueError(
                "boundaries must be strictly increasing positive cut points; "
                f"got {tuple(bl)} (entry {k} = {b} after {lo})")
        lo = int(b)
    if num_layers is not None and lo != num_layers:
        raise ValueError(
            f"last boundary must equal the layer count {num_layers}; "
            f"got {tuple(bl)}")


def stage_lengths(boundaries: Sequence[int]) -> Tuple[int, ...]:
    _check_boundaries(boundaries)
    out, lo = [], 0
    for b in boundaries:
        out.append(b - lo)
        lo = b
    return tuple(out)


def restack_for_stages(slot_params, boundaries: Sequence[int]):
    """(L, ...) stacked layer params -> (S, max_len, ...) with zero padding.

    Zero-padded blocks are exact identity functions of the residual stream
    (all projections zero => zero update).

    Implemented as ONE constant-index gather + mask rather than per-stage
    slice/concat/stack: under jit, GSPMD must repartition this op's output
    onto the pipeline mesh's stage axis, and XLA's SPMD partitioner
    miscompiles the concat-of-slices form on multi-axis (stage x env)
    meshes (wrong layer rows land on stages). A single gather with a
    host-constant index partitions correctly everywhere.
    """
    num_layers = int(jax.tree.leaves(slot_params)[0].shape[0])
    _check_boundaries(boundaries, num_layers=num_layers)
    s = len(boundaries)
    lens = stage_lengths(boundaries)
    max_len = max(lens)
    idx = np.zeros((s, max_len), np.int32)
    mask = np.zeros((s, max_len), bool)
    lo = 0
    for k, b in enumerate(boundaries):
        idx[k, : b - lo] = np.arange(lo, b)
        mask[k, : b - lo] = True
        lo = b
    idx_f = jnp.asarray(idx.reshape(-1))
    mask_f = jnp.asarray(mask.reshape(-1))

    def one(a):
        out = jnp.take(a, idx_f, axis=0)
        m = mask_f.reshape((s * max_len,) + (1,) * (a.ndim - 1))
        return jnp.where(m, out, 0).reshape((s, max_len) + a.shape[1:])

    return jax.tree.map(one, slot_params)


def unstack_stage_grads(stage_grads, boundaries: Sequence[int]):
    """(S, max_len, ...) per-stage grads -> (L, ...) layer layout.

    Inverse of :func:`restack_for_stages`; the zero-padding rows are
    dropped (their gradients are exact zeros - the padded blocks touch
    the residual stream through zeroed projections on both sides).
    Gather-based for the same SPMD-partitioner reason as
    :func:`restack_for_stages`.
    """
    lens = stage_lengths(boundaries)
    s, max_len = len(lens), max(lens)
    idx = jnp.asarray(
        np.concatenate([k * max_len + np.arange(n) for k, n in enumerate(lens)]),
        jnp.int32,
    )

    def one(a):
        flat = a.reshape((s * max_len,) + a.shape[2:])
        return jnp.take(flat, idx, axis=0)

    return jax.tree.map(one, stage_grads)


def unique_signatures(cfg: ModelConfig):
    """Distinct per-layer signatures + per-layer branch codes.

    Returns ``(sig, uniq, codes)``: the full per-layer signature tuple,
    the distinct signatures in first-appearance order (the ``lax.switch``
    branch order of the mixed-block executor), and an ``(L,)`` int32
    array mapping each layer to its branch index. All host constants -
    the block-type schedule is STATIC per split plan.
    """
    sig = M.signature(cfg)
    uniq = []
    for s in sig:
        if s not in uniq:
            uniq.append(s)
    codes = np.asarray([uniq.index(s) for s in sig], np.int32)
    return sig, tuple(uniq), codes


def _sig_field_keys(cfg: ModelConfig, slot_sig) -> Tuple[str, ...]:
    """Top-level param fields a signature's block reads (host constant)."""
    shapes = jax.eval_shape(
        lambda k: M.init_block(k, cfg, slot_sig, jnp.float32),
        jax.random.PRNGKey(0))
    return tuple(shapes.keys())


def union_layer_params(slots, num_layers: int):
    """Per-period slot stacks -> ONE (L, ...) stack in a UNION field layout.

    ``slots`` is ``params["slots"]``: a ``period``-tuple of trees whose
    leading dim is ``L / period`` (layer ``i`` lives in slot ``i % period``
    at row ``i // period``). The union row for a layer carries every
    top-level field any slot in the period uses; fields foreign to the
    layer's own signature are zero-filled and never read by its
    ``lax.switch`` branch (their gradients come back as exact zeros, see
    :func:`split_union_grads`). Field shapes agree across slots because
    every block of a config shares one ``ModelConfig``.
    """
    period = len(slots)
    fields = {}
    for slot in slots:
        for k, v in slot.items():
            fields.setdefault(k, jax.tree.map(
                lambda a: jnp.zeros((num_layers,) + a.shape[1:], a.dtype), v))
    out = {}
    for k, base in fields.items():
        for j, slot in enumerate(slots):
            if k in slot:
                # static-stride scatter: slot j owns layers j, j+p, j+2p, ...
                base = jax.tree.map(
                    lambda b, sv: b.at[j::period].set(sv), base, slot[k])
        out[k] = base
    return out


def split_union_grads(union_grads, slots):
    """(L, ...) union-layout grads -> the ``params["slots"]`` structure.

    Inverse of :func:`union_layer_params`: slot ``j`` takes the static
    strided rows ``[j::period]`` of exactly its own fields; the union's
    foreign-field rows (exact zeros - no switch branch reads them) are
    dropped.
    """
    period = len(slots)
    out = []
    for j, slot in enumerate(slots):
        out.append({
            k: jax.tree.map(lambda a: a[j::period], union_grads[k])
            for k in slot
        })
    return tuple(out)


def _stage_codes(layer_codes: np.ndarray, boundaries: Sequence[int]):
    """(L,) per-layer branch codes -> (S, max_len) per-stage schedule.

    Same layout as :func:`restack_for_stages`; padding slots get code 0
    but are masked by the stage's active length before dispatch.
    """
    lens = stage_lengths(boundaries)
    s, max_len = len(lens), max(lens)
    out = np.zeros((s, max_len), np.int32)
    lo = 0
    for k, b in enumerate(boundaries):
        out[k, : b - lo] = layer_codes[lo:b]
        lo = b
    return jnp.asarray(out)


def pipeline_loss_fn(cfg: ModelConfig, mesh: Mesh, boundaries: Sequence[int],
                     n_microbatches: int, stage_axis: str = "stage",
                     pipe: Optional[PipelineConfig] = None,
                     env_axis: Optional[str] = None):
    """Build the fill-drain (GPipe) pipelined LM loss - the REFERENCE path.

    (params, tokens, labels) -> scalar loss; backward comes from
    ``jax.grad`` reversing the scan. tokens: (M * mb, T). The schedule
    runs M + S - 1 ticks; each tick every stage applies its (padded)
    blocks and ppermutes the activation to the next stage. The 1F1B
    executor (:func:`pipeline_step_fn`) is gradient-compatible with this
    function at rtol <= 2e-5 and is what the benchmarks race against it.

    ``env_axis``: on a 2-D (stage x env) mesh, shard the microbatch ROW
    dim over this axis (data parallelism composed with the pipeline);
    the loss is ``pmean``-ed over it. It leaves out the MoE router's aux
    loss, which the 1F1B step adds.
    """
    sig = M.signature(cfg)
    period = M.find_period(sig)
    assert period == 1, (
        f"fill-drain reference needs period-1 archs, got {period}; "
        "mixed block types run through the 1f1b schedule")
    slot_sig = sig[0]
    s_stages = len(boundaries)
    max_len = max(stage_lengths(boundaries))
    blk_impl = pipe.block_impl if pipe is not None else "auto"
    act_dtype = pipe.dtype if pipe is not None else jnp.bfloat16
    env_size = int(mesh.shape[env_axis]) if env_axis is not None else 1

    def fn(params, tokens, labels):
        stage_blocks = restack_for_stages(params["slots"][0], boundaries)
        m_total, t_len = tokens.shape
        mb = m_total // n_microbatches
        if mb % env_size:
            raise ValueError(
                f"microbatch size {mb} must divide over env axis ({env_size})")
        tok_mb = tokens.reshape(n_microbatches, mb, t_len)
        lab_mb = labels.reshape(n_microbatches, mb, t_len)

        def per_stage(stage_blocks, tok_mb, lab_mb, embed, final_norm, head):
            stage_blocks = jax.tree.map(lambda a: a[0], stage_blocks)  # drop S dim
            mb = tok_mb.shape[1]  # LOCAL rows (sharded over env_axis)
            sidx = jax.lax.axis_index(stage_axis)
            positions = jnp.arange(t_len)

            def apply_stage(x):
                for i in range(max_len):
                    blk = jax.tree.map(lambda a: a[i], stage_blocks)
                    x, _, _ = M.block_apply(
                        blk, x, cfg, slot_sig, positions=positions, cache=None,
                        cache_index=None, impl=blk_impl,
                    )
                return x

            # loss accumulators are (1,)-shaped, not scalars: they differ
            # across stages (only the last stage emits loss), and shard_map's
            # partial-eval cannot concatenate rank-0 residuals that vary over
            # the mesh - jax.grad through the pipeline needs the singleton
            # axis (see test_pipeline_matches_reference).
            def tick(carry, t):
                x, loss_acc, nloss = carry
                # stage 0 ingests microbatch t (if valid)
                mb_in_idx = jnp.clip(t, 0, n_microbatches - 1)
                fresh = embed[tok_mb[mb_in_idx]].astype(x.dtype)
                x = jnp.where((sidx == 0) & (t < n_microbatches), fresh, x)
                x = apply_stage(x)
                # last stage emits loss for microbatch t - (S-1)
                mb_out = t - (s_stages - 1)
                is_out = (sidx == s_stages - 1) & (mb_out >= 0)
                xh = L.rms_norm(x, final_norm, cfg.norm_eps)
                logits = jnp.einsum("bsd,dv->bsv", xh, head.astype(x.dtype))
                lab = lab_mb[jnp.clip(mb_out, 0, n_microbatches - 1)]
                li = M.softmax_xent(logits, lab)
                loss_acc = loss_acc + jnp.where(is_out, li, 0.0)[None]
                nloss = nloss + jnp.where(is_out, 1.0, 0.0)[None]
                # hop to the next stage (the multi-hop transmission, Eq. 1)
                perm = [(i, (i + 1) % s_stages) for i in range(s_stages)]
                x = jax.lax.ppermute(x, stage_axis, perm)
                return (x, loss_acc, nloss), None

            x0 = jnp.zeros((mb, t_len, cfg.d_model), act_dtype)
            ticks = n_microbatches + s_stages - 1
            (x, loss_acc, nloss), _ = jax.lax.scan(
                tick, (x0, jnp.zeros((1,)), jnp.zeros((1,))), jnp.arange(ticks)
            )
            # broadcast the last stage's mean loss to everyone
            total = jax.lax.psum(loss_acc, stage_axis)
            cnt = jax.lax.psum(nloss, stage_axis)
            loss = (total / jnp.maximum(cnt, 1.0))[0]
            if env_axis is not None:
                loss = jax.lax.pmean(loss, env_axis)
            return loss

        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        data_spec = P(None, env_axis) if env_axis is not None else P()
        loss = jax.shard_map(
            per_stage,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(stage_axis), stage_blocks),
                data_spec, data_spec, P(), P(), P(),
            ),
            out_specs=P(),
            check_vma=False,
        )(stage_blocks, tok_mb, lab_mb, params["embed"], params["final_norm"], head)
        return loss

    return fn


def pipeline_step_fn(cfg: ModelConfig, mesh: Mesh, boundaries: Sequence[int],
                     n_microbatches: int, stage_axis: str = "stage",
                     pipe: PipelineConfig = PipelineConfig(),
                     env_axis: Optional[str] = None):
    """Build the pipelined train step: (params, tokens, labels) -> (loss, grads).

    ``pipe.schedule == "1f1b"`` runs the interleaved schedule described in
    the module docstring; ``"fill_drain"`` wraps the reference loss in
    ``jax.value_and_grad`` (useful as the benchmark baseline and parity
    oracle). Gradients come back in the exact ``params`` pytree structure
    (zero for untouched leaves such as frontends).

    1F1B mechanics (S stages, M microbatches, T = M + 2(S-1) ticks,
    stash depth D = 2(S-1) + 1):

    * tick ``t``, stage ``i`` FORWARDS microbatch ``t - i`` (when in
      ``[0, M)``) and BACKWARDS microbatch ``t - 2(S-1) + i`` - the last
      stage runs its forward and backward of the same microbatch
      back-to-back in one tick, which is what shortens the schedule to
      ``M + 2(S-1)`` ticks against fill-drain's ``2(M + S - 1)``.
    * a stage's forward stashes only its INPUT activation; the backward
      slot re-runs the stage forward under ``jax.vjp`` (rematerialized
      backward), keeping the stash O(S) deep.
    * the forward slot is skipped on the last stage (its loss VJP
      recomputes it), so the final-norm + LM-head + loss run ONCE per
      microbatch instead of on every stage every tick.
    * per-stage block grads accumulate sharded (out_spec along the stage
      axis) and are re-laid-out to the (L, ...) slot layout host-side;
      embed/final-norm/head grads are psum'd across stages.
    * ``pipe.transport`` picks the handoff: ``"overlap"`` carries the
      wire-dtype send buffers through the scan and issues both
      ``ppermute``s at the TOP of the next tick (before its compute, so
      XLA can run them as async collectives under the opposite slot);
      ``"sync"`` hops at the end of the tick on its fresh outputs. Both
      consume each buffer exactly one tick after it is produced, so they
      compute the same function.
    * ``env_axis``: on a 2-D (stage x env) mesh, shard the microbatch ROW
      dim over this axis; loss and grads are ``pmean``-ed over it after
      the stage-axis reductions.
    * a model with MoE blocks adds each microbatch's router aux loss
      (``block_apply``'s ``stats["aux"]``, every stage's layers) to the
      loss, and the step returns ``(loss, grads, moe_rows)``: ``moe_rows``
      (L, held) int32, the rows each layer routed to each expert it holds,
      summed over the step's microbatches (zeros for non-MoE layers).
    """
    if pipe.schedule == "fill_drain":
        loss_fn = pipeline_loss_fn(cfg, mesh, boundaries, n_microbatches,
                                   stage_axis, pipe=pipe, env_axis=env_axis)

        def fd_step(params, tokens, labels):
            return jax.value_and_grad(loss_fn)(params, tokens, labels)

        return fd_step
    assert pipe.schedule == "1f1b", pipe.schedule

    sig, uniq_sigs, layer_codes = unique_signatures(cfg)
    period = M.find_period(sig)
    mixed = period > 1
    slot_sig = sig[0]
    moe = any(is_moe for _, is_moe, _ in sig)
    held = cfg.moe.held  # 0 for a dense model: its routed-row counts are empty
    uniq_keys = [_sig_field_keys(cfg, u) for u in uniq_sigs]
    s_stages = len(boundaries)
    lens = stage_lengths(boundaries)
    max_len = max(lens)
    m_micro = n_microbatches
    n_ticks = m_micro + 2 * (s_stages - 1)
    depth = 2 * (s_stages - 1) + 1  # activation-stash ring depth
    blk_impl = pipe.block_impl
    wdtype = pipe.wire
    overlap = pipe.transport == "overlap"
    env_size = int(mesh.shape[env_axis]) if env_axis is not None else 1
    # tied embeddings: the LM head IS the (V, d) embedding table, read in
    # its own layout (no transposed copy), and its loss gradient lands in
    # the embedding-gradient accumulator - at a 152k vocabulary each extra
    # (V, d) f32 buffer is 1.2 GB of a 16 GB chip
    tied = cfg.tie_embeddings
    head_spec = "bsd,vd->bsv" if tied else "bsd,dv->bsv"

    # the whole step, so the stage re-layout and the schedule's own loop
    # are attributed too; the 1F1B slots below carry their own scopes
    @jax.named_scope("pipeline.step")
    def fn(params, tokens, labels):
        if mixed:
            layer_stack = union_layer_params(params["slots"], cfg.num_layers)
        else:
            layer_stack = params["slots"][0]
        stage_blocks = restack_for_stages(layer_stack, boundaries)
        codes_st = _stage_codes(layer_codes, boundaries)  # (S, max_len)
        lens_arr = jnp.asarray(lens, jnp.int32)
        m_total, t_len = tokens.shape
        mb = m_total // m_micro
        if mb % env_size:
            raise ValueError(
                f"microbatch size {mb} must divide over env axis ({env_size})")
        tok_mb = tokens.reshape(m_micro, mb, t_len)
        lab_mb = labels.reshape(m_micro, mb, t_len)
        head = params["embed"] if tied else params["lm_head"]

        def per_stage(stage_blocks, codes_st, lens_arr, tok_mb, lab_mb, embed,
                      final_norm, head):
            stage_blocks = jax.tree.map(lambda a: a[0], stage_blocks)
            codes = codes_st[0]  # (max_len,) this stage's block-kind schedule
            mb = tok_mb.shape[1]  # LOCAL rows (sharded over env_axis)
            active_len = lens_arr[0]
            sidx = jax.lax.axis_index(stage_axis)
            is_first = sidx == 0
            is_last = sidx == s_stages - 1
            positions = jnp.arange(t_len)

            def block_out(out, st):
                # every block gives its aux loss and routed rows (0 and
                # zeros where it has no experts)
                return out, (st["aux"], st.get(
                    "moe_rows", jnp.zeros((held,), jnp.int32)))

            if mixed:
                # one switch branch per distinct signature; each reads ONLY
                # its own fields of the union row, so the foreign zero-filled
                # fields transpose to exact-zero gradients
                branches = []
                for u, keys in zip(uniq_sigs, uniq_keys):
                    def br(blk, xx, _u=u, _keys=keys):
                        sub = {k: blk[k] for k in _keys}
                        out, _, st = M.block_apply(
                            sub, xx, cfg, _u, positions=positions,
                            cache=None, cache_index=None, impl=blk_impl,
                        )
                        return block_out(out, st)
                    branches.append(br)

                def apply_block(blk, code, xx):
                    return jax.lax.switch(code, branches, blk, xx)
            else:
                def apply_block(blk, code, xx):
                    out, _, st = M.block_apply(
                        blk, xx, cfg, slot_sig, positions=positions,
                        cache=None, cache_index=None, impl=blk_impl,
                    )
                    return block_out(out, st)

            def stage_fwd(blocks, x):
                """x -> (the stage's output, its aux loss, (max_len, held)
                routed rows)."""
                # scan over the padded block stack; the cond masks compute
                # down to the stage's ACTIVE length (padding blocks are
                # exact identities, so skipping them is value-preserving)
                def body(xc, blk_code_i):
                    blk, code, i = blk_code_i
                    return jax.lax.cond(
                        i < active_len,
                        lambda xx: apply_block(blk, code, xx),
                        lambda xx: (xx, (jnp.zeros((), jnp.float32),
                                         jnp.zeros((held,), jnp.int32))),
                        xc)

                out, st = jax.lax.scan(
                    body, x, (blocks, codes, jnp.arange(max_len)))
                return out, st[0].sum(), st[1]

            def stage_loss(blocks, fnorm, hd, x, lab):
                y, aux, rows = stage_fwd(blocks, x)
                with jax.named_scope("pipeline.head"):
                    xh = L.rms_norm(y, fnorm, cfg.norm_eps)
                    logits = jnp.einsum(head_spec, xh, hd.astype(y.dtype))
                    loss = M.softmax_xent(logits, lab)
                return loss + aux, rows

            perm_f = [(i, (i + 1) % s_stages) for i in range(s_stages)]
            perm_b = [(i, (i - 1) % s_stages) for i in range(s_stages)]

            def tick(carry, t):
                # acc = (gblocks, gembed, gnorm, ghead, loss_acc, rows)
                buf_x, buf_g, stash, acc = carry

                # ---- the hops (Eq. 1 forward, Eq. 4 gradient) -------------
                # overlap: the carry holds LAST tick's wire-dtype send
                # buffers; issuing both ppermutes here, before any of this
                # tick's block compute, lets XLA schedule them as async
                # collective-permute-start/done pairs that run under the
                # slot that does not consume them.
                if overlap:
                    with jax.named_scope("pipeline.hop"):
                        x_in = jax.lax.ppermute(
                            buf_x, stage_axis, perm_f).astype(pipe.dtype)
                        g_in = jax.lax.ppermute(
                            buf_g, stage_axis, perm_b).astype(pipe.dtype)
                else:
                    x_in, g_in = buf_x, buf_g

                # ---- forward slot: microbatch t - i -----------------------
                with jax.named_scope("pipeline.fwd"):
                    mf = t - sidx
                    f_valid = (mf >= 0) & (mf < m_micro)
                    # the embedding gather is stage 0's alone - cond it out
                    # on the other S-1 stages instead of masking it to zeros
                    x0 = jax.lax.cond(
                        is_first,
                        lambda xx: embed[
                            tok_mb[jnp.clip(mf, 0, m_micro - 1)]
                        ].astype(xx.dtype),
                        lambda xx: xx,
                        x_in,
                    )
                    stash = jax.lax.cond(
                        f_valid,
                        lambda st: jax.lax.dynamic_update_index_in_dim(
                            st, x0, jnp.mod(mf, depth), 0
                        ),
                        lambda st: st,
                        stash,
                    )
                    # the last stage's forward happens inside its loss VJP,
                    # so its forward slot only stashes
                    y = jax.lax.cond(
                        f_valid & (~is_last),
                        lambda xx: stage_fwd(stage_blocks, xx)[0],
                        lambda xx: xx,
                        x0,
                    )

                # ---- backward slot: microbatch t - 2(S-1) + i -------------
                mbk = t - 2 * (s_stages - 1) + sidx
                b_valid = (mbk >= 0) & (mbk < m_micro)
                mb_c = jnp.clip(mbk, 0, m_micro - 1)
                x_saved = jax.lax.dynamic_index_in_dim(
                    stash, jnp.mod(mbk, depth), 0, keepdims=False
                )
                lab = lab_mb[mb_c]
                toksb = tok_mb[mb_c]

                # the accumulators ride INTO the conds and come back
                # updated: a branch that does no work hands them back
                # untouched instead of materializing weight-sized zeros
                def run_bwd(operand):
                    x_sv, g, lb, acc = operand

                    def seed():
                        return jnp.asarray(1.0 / m_micro, jnp.float32)

                    def last_branch(acc):
                        gblocks, gembed, gnorm, ghead, loss_acc, racc = acc
                        li, vjp, rows = jax.vjp(
                            lambda bl, fn_, hd_, xx: stage_loss(bl, fn_, hd_, xx, lb),
                            stage_blocks, final_norm, head, x_sv, has_aux=True,
                        )
                        dbl, dfn, dhd, dx = vjp(seed())
                        with jax.named_scope("pipeline.accum"):
                            if tied:
                                gembed = gembed + dhd
                            else:
                                ghead = ghead + dhd
                            return (jax.tree.map(jnp.add, gblocks, dbl),
                                    gembed, gnorm + dfn, ghead, loss_acc + li,
                                    racc + rows), dx

                    def mid_branch(acc):
                        gblocks, gembed, gnorm, ghead, loss_acc, racc = acc
                        # the stage's aux loss is part of the loss too
                        (_, a), vjp, rows = jax.vjp(
                            lambda bl, xx: (lambda o: (o[:2], o[2]))(
                                stage_fwd(bl, xx)),
                            stage_blocks, x_sv, has_aux=True)
                        dbl, dx = vjp((g, seed()))
                        with jax.named_scope("pipeline.accum"):
                            return (jax.tree.map(jnp.add, gblocks, dbl),
                                    gembed, gnorm, ghead, loss_acc + a,
                                    racc + rows), dx

                    return jax.lax.cond(is_last, last_branch, mid_branch, acc)

                def skip_bwd(operand):
                    x_sv, g, _lb, acc = operand
                    return acc, jnp.zeros_like(g)

                with jax.named_scope("pipeline.bwd"):
                    acc, dx = jax.lax.cond(
                        b_valid, run_bwd, skip_bwd, (x_saved, g_in, lab, acc)
                    )
                gblocks, gembed, gnorm, ghead, loss_acc, racc = acc
                # stage 0's dx is the cotangent of the embedding lookup;
                # the full-vocab scatter-add is cond-gated like the other
                # idle slots (it would otherwise run masked-to-zero on
                # every stage every tick)
                with jax.named_scope("pipeline.accum"):
                    gembed = jax.lax.cond(
                        b_valid & is_first,
                        lambda ge: ge.at[toksb].add(dx.astype(ge.dtype)),
                        lambda ge: ge,
                        gembed,
                    )
                acc = (gblocks, gembed, gnorm, ghead, loss_acc, racc)

                if overlap:
                    # stage outputs become NEXT tick's in-flight buffers
                    x_next = y.astype(wdtype)
                    g_next = dx.astype(wdtype)
                else:
                    # synchronous handoff: hop now, on this tick's outputs
                    with jax.named_scope("pipeline.hop"):
                        x_next = jax.lax.ppermute(y.astype(wdtype), stage_axis,
                                                  perm_f).astype(pipe.dtype)
                        g_next = jax.lax.ppermute(dx.astype(wdtype), stage_axis,
                                                  perm_b).astype(pipe.dtype)
                return (x_next, g_next, stash, acc), None

            buf_dtype = wdtype if overlap else pipe.dtype
            x0 = jnp.zeros((mb, t_len, cfg.d_model), buf_dtype)
            g0 = jnp.zeros_like(x0)
            stash0 = jnp.zeros((depth, mb, t_len, cfg.d_model), pipe.dtype)
            acc0 = (
                jax.tree.map(jnp.zeros_like, stage_blocks),
                jnp.zeros_like(embed),
                jnp.zeros_like(final_norm),
                # tied: head grads go to gembed; a scalar placeholder here
                jnp.zeros((), jnp.float32) if tied else jnp.zeros_like(head),
                jnp.zeros((), jnp.float32),
                jnp.zeros((max_len, held), jnp.int32),
            )
            (_, _, _, acc), _ = jax.lax.scan(
                tick, (x0, g0, stash0, acc0), jnp.arange(n_ticks)
            )
            gblocks, gembed, gnorm, ghead, loss_acc, rows = acc
            loss = jax.lax.psum(loss_acc, stage_axis) / m_micro
            gembed = jax.lax.psum(gembed, stage_axis)
            gnorm = jax.lax.psum(gnorm, stage_axis)
            ghead = jax.lax.psum(ghead, stage_axis)
            if env_axis is not None:
                # data-parallel reduction: every env shard saw mb/env_size
                # rows of each microbatch, so the mean-of-means is the mean
                loss = jax.lax.pmean(loss, env_axis)
                gblocks = jax.lax.pmean(gblocks, env_axis)
                gembed = jax.lax.pmean(gembed, env_axis)
                gnorm = jax.lax.pmean(gnorm, env_axis)
                ghead = jax.lax.pmean(ghead, env_axis)
                rows = jax.lax.psum(rows, env_axis)
            return (loss, jax.tree.map(lambda a: a[None], gblocks), gembed,
                    gnorm, ghead, rows[None])

        data_spec = P(None, env_axis) if env_axis is not None else P()
        loss, gstages, gembed, gnorm, ghead, rows = jax.shard_map(
            per_stage,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(stage_axis), stage_blocks),
                P(stage_axis), P(stage_axis), data_spec, data_spec,
                P(), P(), P(),
            ),
            out_specs=(
                P(),
                jax.tree.map(lambda _: P(stage_axis), stage_blocks),
                P(), P(), P(), P(stage_axis),
            ),
            check_vma=False,
        )(stage_blocks, codes_st, lens_arr, tok_mb, lab_mb, params["embed"],
          params["final_norm"], head)

        grads = jax.tree.map(jnp.zeros_like, params)
        union_grads = unstack_stage_grads(gstages, boundaries)
        if mixed:
            grads["slots"] = split_union_grads(union_grads, params["slots"])
        else:
            grads["slots"] = (union_grads,)
        grads["final_norm"] = gnorm
        grads["embed"] = gembed
        if not tied:
            grads["lm_head"] = ghead
        if not moe:  # callers of a dense step take (loss, grads)
            return loss, grads
        return loss, grads, unstack_stage_grads(rows, boundaries)

    return fn


# ---------------------------------------------------------------------------
# serving: decode-mode stage pass (per-stage KV rings)
# ---------------------------------------------------------------------------


def stage_kv_caches(cfg: ModelConfig, boundaries: Sequence[int],
                    num_slots: int, cache_len: int, dtype=jnp.float32):
    """Per-stage KV rings for pipelined serving.

    Returns ``{"k", "v"}`` of shape ``(S, max_len, B, kv_len, KH, hd)`` -
    stage ``k``'s ring holds ONLY its own layers' KV entries (row ``i`` of
    stage ``k`` is global layer ``boundaries[k-1] + i``; padding rows
    belong to the zero-identity padding blocks and stay zero). Shard with
    ``P(stage_axis)`` on the leading dim - the cache never leaves its
    stage, exactly like the paper's sub-model state never leaves its
    device.
    """
    sig = M.signature(cfg)
    if any(kind != "A" for kind, _, _ in sig):
        raise ValueError("stage_kv_caches: attention-only archs")
    lens = stage_lengths(boundaries)
    s, max_len = len(lens), max(lens)
    kv_len = (min(cache_len, cfg.attention_window)
              if cfg.attention_window is not None else cache_len)
    shape = (s, max_len, num_slots, kv_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def pipeline_serve_fns(cfg: ModelConfig, mesh: Mesh, boundaries: Sequence[int],
                       stage_axis: str = "stage",
                       pipe: PipelineConfig = PipelineConfig(
                           compute_dtype="float32")):
    """Build the decode-mode stage passes for the serving engine.

    Returns ``(prefill, decode)`` with the engine's runner signatures:

    * ``prefill(params, caches, prompts, last=None)``: ``prompts`` (B, P)
      -> ``(logits (B, P, V), caches)`` - a fresh-sequence pass (scalar
      cache index 0) through all stages; with ``last`` (B,) positions
      only those rows reach the head and the logits are (B, V).
    * ``decode(params, tok, caches, pos)``: ``tok`` (B, 1), ``pos`` (B,)
      per-slot entry counts -> ``(logits (B, V), caches)`` - one token
      through the token ring.

    Both run the serial token ring: S ticks, tick ``t`` computes on stage
    ``t`` (``lax.cond`` on the stage index - padding blocks and foreign
    ticks skip their FLOPs) while the activation hop (``ppermute``, the
    Eq. 1 transmission) fires unconditionally every tick, cast to
    ``pipe.wire_dtype`` on the wire. Decode is SERIAL by construction:
    the sampled token feeds back into stage 0, so consecutive tokens
    cannot pipeline - the multi-hop latency the paper's Eq. 5-7 charges
    per inference. Logits replicate off the last stage via a masked
    ``psum`` (exact: the other stages contribute exact zeros).

    The hops stay OUTSIDE every ``cond`` so each stage executes the same
    collective sequence regardless of which slot is live - that is what
    keeps the engine step one compiled trace across arrivals/completions.
    """
    sig, uniq_sigs, layer_codes = unique_signatures(cfg)
    period = M.find_period(sig)
    mixed = period > 1
    slot_sig = sig[0]
    if any(kind != "A" for kind, _, _ in sig):
        raise ValueError(
            "pipeline serving: SSM/hybrid archs are unservable - padded "
            "batched prefill relies on causal masking, which protects KV "
            "attention but not recurrent scan state")
    uniq_keys = [_sig_field_keys(cfg, u) for u in uniq_sigs]
    s_stages = len(boundaries)
    lens = stage_lengths(boundaries)
    max_len = max(lens)
    blk_impl = pipe.block_impl
    wdtype = pipe.wire
    perm_f = [(i, (i + 1) % s_stages) for i in range(s_stages)]

    def _ring_pass(params, caches, x, positions, cache_index, last=None):
        """Token-ring forward: x (B, s, d) embedded input (live on stage 0).

        Returns (logits (B, s, V), caches), or with ``last`` (B,) positions
        (logits (B, V), caches) with only those rows through the head.
        Runs under shard_map."""
        if mixed:
            layer_stack = union_layer_params(params["slots"], cfg.num_layers)
        else:
            layer_stack = params["slots"][0]
        stage_blocks = restack_for_stages(layer_stack, boundaries)
        codes_st = _stage_codes(layer_codes, boundaries)
        lens_arr = jnp.asarray(lens, jnp.int32)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]

        gather = last is not None
        if not gather:
            last = jnp.zeros((x.shape[0],), jnp.int32)

        def per_stage(stage_blocks, codes_st, lens_arr, ck, cv, x, last,
                      embed, final_norm, head):
            stage_blocks = jax.tree.map(lambda a: a[0], stage_blocks)
            codes = codes_st[0]
            ck, cv = ck[0], cv[0]  # (max_len, B, kv, KH, hd)
            active_len = lens_arr[0]
            sidx = jax.lax.axis_index(stage_axis)

            if mixed:
                # all signatures are kind "A" here (gated above), so every
                # switch branch threads the same-shaped KV ring; dense vs
                # MoE MLP halves differ per branch
                branches = []
                for u, keys in zip(uniq_sigs, uniq_keys):
                    def br(blk, xi, ki, vi, _u=u, _keys=keys):
                        sub = {k: blk[k] for k in _keys}
                        out, nc, _ = M.block_apply(
                            sub, xi, cfg, _u, positions=positions,
                            cache={"k": ki, "v": vi},
                            cache_index=cache_index, impl=blk_impl,
                        )
                        return out, nc["k"], nc["v"]
                    branches.append(br)

                def apply_block(blk, code, xi, ki, vi):
                    return jax.lax.switch(code, branches, blk, xi, ki, vi)
            else:
                def apply_block(blk, code, xi, ki, vi):
                    out, nc, _ = M.block_apply(
                        blk, xi, cfg, slot_sig, positions=positions,
                        cache={"k": ki, "v": vi},
                        cache_index=cache_index, impl=blk_impl,
                    )
                    return out, nc["k"], nc["v"]

            def stage_apply(operand):
                xx, ck, cv = operand

                def body(carry, blk_cache_i):
                    xc, = carry
                    blk, k_i, v_i, code, i = blk_cache_i

                    def apply(op):
                        xi, ki, vi = op
                        return apply_block(blk, code, xi, ki, vi)

                    with jax.named_scope("model.block"):
                        xc, k_i, v_i = jax.lax.cond(
                            i < active_len, apply, lambda op: op,
                            (xc, k_i, v_i))
                    return (xc,), (k_i, v_i)

                # the scan's own slicing and stacking of the stage's
                # cache is model.layers less model.block (models.model's
                # forward carries its cache and writes it in place)
                with jax.named_scope("model.layers"):
                    (xx,), (nk, nv) = jax.lax.scan(
                        body, (xx,), (stage_blocks, ck, cv, codes,
                                      jnp.arange(max_len)))
                return xx, nk, nv

            for t in range(s_stages):
                if t > 0:
                    # the hop: Eq. 1 transmission, wire-dtype bytes
                    x = jax.lax.ppermute(
                        x.astype(wdtype), stage_axis, perm_f
                    ).astype(pipe.dtype)
                x, ck, cv = jax.lax.cond(
                    sidx == t, stage_apply, lambda op: op, (x, ck, cv))

            if gather:
                x = jnp.take_along_axis(x, last[:, None, None], axis=1)
            xh = L.rms_norm(x, final_norm, cfg.norm_eps)
            logits = jnp.einsum("bsd,dv->bsv", xh, head.astype(x.dtype))
            is_last = (sidx == s_stages - 1)
            logits = jax.lax.psum(
                jnp.where(is_last, logits.astype(jnp.float32), 0.0),
                stage_axis)
            return logits, ck[None], cv[None]

        logits, ck, cv = jax.shard_map(
            per_stage,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(stage_axis), stage_blocks),
                P(stage_axis), P(stage_axis), P(stage_axis), P(stage_axis),
                P(), P(), P(), P(), P(),
            ),
            out_specs=(P(), P(stage_axis), P(stage_axis)),
            check_vma=False,
        )(stage_blocks, codes_st, lens_arr, caches["k"], caches["v"], x,
          last, params["embed"], params["final_norm"], head)
        if gather:
            logits = logits[:, 0]
        return logits, {"k": ck, "v": cv}

    def prefill(params, caches, prompts, last=None):
        x = params["embed"].astype(pipe.dtype)[prompts]
        positions = jnp.arange(prompts.shape[1])
        return _ring_pass(params, caches, x, positions,
                          jnp.zeros((), jnp.int32), last)

    def decode(params, tok, caches, pos):
        x = params["embed"].astype(pipe.dtype)[tok]
        positions = pos[:, None]  # (B, 1) per-row
        logits, caches = _ring_pass(params, caches, x, positions, pos)
        return logits[:, -1], caches

    return prefill, decode
