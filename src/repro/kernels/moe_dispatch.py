"""Grouped expert-FFN paths for dropless MoE dispatch.

On a TPU ``layers.moe_dropless`` runs :func:`grouped_ffn_ragged`
(``lax.ragged_dot`` over the expert-sorted rows, no padding). On other
backends it runs :func:`grouped_ffn_reference` over a block-padded
layout, and the Pallas kernel below is its CPU-tested fused variant
(the TPU compiler refuses its ``(1,)`` expert-id block and whole-bank
weight loads at published widths).

``layers.moe_apply_dropless`` sorts the (T*k) routed token copies by
expert id and packs them into per-expert regions padded to ``blk``-row
blocks, so every grid step processes ONE ``(blk, D)`` row tile that
belongs to exactly one expert. This kernel runs the expert FFN over that
padded buffer:

  * grid ``(n_blocks,)``; each step reads its ``(blk, D)`` tile plus a
    one-element ``block_eid`` tile naming the owning expert, and the
    expert weight stacks ride along whole (``(E, D, F)``/``(E, F, D)``
    fit VMEM at split-executor sizes - a TPU production variant would
    swap the whole-stack loads for scalar-prefetch weight BlockSpecs);
  * per tile: up/gate matmuls, activation, down-projection, all with
    ``preferred_element_type=jnp.float32`` - no HBM round-trip between
    them. Padding rows are zero; FFN(0) rows are never gathered back.

The backward pass is the jax AD of ``grouped_ffn_reference`` (the
mathematically-identical gathered-weight batched einsum), the same
custom-VJP pattern as ``stage_block`` - pallas_call has no transpose
rule, so gradients are reference-exact by construction.

``interpret=None`` resolves from the backend (compiled on TPU, Pallas
interpreter elsewhere). Forward AND grad are validated bitwise against
the dense per-expert reference in ``tests/test_moe_dropless.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _act(name: str, g, u):
    if name == "swiglu":
        return jax.nn.silu(g) * u
    if name == "gelu":
        return jax.nn.gelu(u)
    if name == "relu2":
        return jnp.square(jax.nn.relu(u))
    if name == "silu":
        return jax.nn.silu(u)
    raise KeyError(name)


def grouped_ffn_reference(buf, block_eid, w_gate, w_up, w_down,
                          activation: str):
    """Expert FFN over a block-padded expert-sorted buffer, pure jnp.

    ``buf``: (P, D) rows grouped so rows ``[i*blk, (i+1)*blk)`` all belong
    to expert ``block_eid[i]``; ``block_eid``: (n_blocks,) int32. Weights
    are the ``init_moe`` stacks (``w_gate`` may be None). Returns (P, D).
    """
    nb = block_eid.shape[0]
    p, d = buf.shape
    blk = p // nb
    xb = buf.reshape(nb, blk, d)
    dt = buf.dtype
    wu = w_up.astype(dt)[block_eid]      # (nb, D, F)
    wd = w_down.astype(dt)[block_eid]    # (nb, F, D)
    if activation == "swiglu":
        wg = w_gate.astype(dt)[block_eid]
        g = jnp.einsum("nbd,ndf->nbf", xb, wg,
                       preferred_element_type=jnp.float32).astype(dt)
        u = jnp.einsum("nbd,ndf->nbf", xb, wu,
                       preferred_element_type=jnp.float32).astype(dt)
    else:
        g = None
        u = jnp.einsum("nbd,ndf->nbf", xb, wu,
                       preferred_element_type=jnp.float32).astype(dt)
    h = _act(activation, g, u).astype(dt)
    out = jnp.einsum("nbf,nfd->nbd", h, wd,
                     preferred_element_type=jnp.float32)
    return out.reshape(p, d).astype(dt)


def grouped_ffn_ragged(rows, group_sizes, w_gate, w_up, w_down,
                       activation: str):
    """Expert FFN over expert-sorted rows with ``lax.ragged_dot``.

    ``rows``: (R, D), the first ``group_sizes[0]`` rows belong to expert 0,
    the next ``group_sizes[1]`` to expert 1, ...; rows past
    ``sum(group_sizes)`` belong to no expert and come back as zeros. On a
    TPU each product is one grouped-matmul kernel that reads each expert's
    weights once and skips the row tiles past the groups, so its work
    follows the routed rows, not ``R``; its gradient is a ragged product
    too (no per-block weight copies). Returns (R, D) in the rows' dtype
    (accumulation in float32).

    A TPU kernel leaves the rows past the groups unwritten, in its output
    and in its gradient for ``rows``: whatever memory held, NaN included.
    So each product's output is masked to the grouped rows before it is
    used, and the cotangent of ``rows`` is masked on the way back.
    """
    dt = rows.dtype
    live = (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]

    def mm(a, w):
        out = jax.lax.ragged_dot(a, w.astype(dt), group_sizes,
                                 preferred_element_type=dt)
        return jnp.where(live, out, 0)

    rows = jnp.where(live, rows, 0)
    g = mm(rows, w_gate) if activation == "swiglu" else None
    h = _act(activation, g, mm(rows, w_up)).astype(dt)
    return mm(h, w_down)


def _kernel_gated(x_ref, eid_ref, wg_ref, wu_ref, wd_ref, out_ref, *,
                  activation: str):
    x = x_ref[...]  # (blk, D)
    dt = x.dtype
    e = eid_ref[0]
    wg = jax.lax.dynamic_index_in_dim(wg_ref[...].astype(dt), e, 0, False)
    wu = jax.lax.dynamic_index_in_dim(wu_ref[...].astype(dt), e, 0, False)
    wd = jax.lax.dynamic_index_in_dim(wd_ref[...].astype(dt), e, 0, False)
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32).astype(dt)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32).astype(dt)
    h = _act(activation, g, u).astype(dt)
    out_ref[...] = jnp.dot(
        h, wd, preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _kernel_plain(x_ref, eid_ref, wu_ref, wd_ref, out_ref, *,
                  activation: str):
    x = x_ref[...]
    dt = x.dtype
    e = eid_ref[0]
    wu = jax.lax.dynamic_index_in_dim(wu_ref[...].astype(dt), e, 0, False)
    wd = jax.lax.dynamic_index_in_dim(wd_ref[...].astype(dt), e, 0, False)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32).astype(dt)
    h = _act(activation, None, u).astype(dt)
    out_ref[...] = jnp.dot(
        h, wd, preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _forward(buf, block_eid, w_gate, w_up, w_down, activation: str,
             interpret: bool):
    p, d = buf.shape
    nb = block_eid.shape[0]
    blk = p // nb
    e, _, f = w_up.shape
    row_spec = pl.BlockSpec((blk, d), lambda i: (i, 0))
    eid_spec = pl.BlockSpec((1,), lambda i: (i,))
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    if activation == "swiglu":
        kernel = functools.partial(_kernel_gated, activation=activation)
        in_specs = [row_spec, eid_spec, whole((e, d, f)), whole((e, d, f)),
                    whole((e, f, d))]
        args = (buf, block_eid, w_gate, w_up, w_down)
    else:
        kernel = functools.partial(_kernel_plain, activation=activation)
        in_specs = [row_spec, eid_spec, whole((e, d, f)), whole((e, f, d))]
        args = (buf, block_eid, w_up, w_down)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((p, d), buf.dtype),
        interpret=interpret,
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped(buf, block_eid, w_gate, w_up, w_down, activation, interpret):
    return _forward(buf, block_eid, w_gate, w_up, w_down, activation,
                    interpret)


def _grouped_fwd(buf, block_eid, w_gate, w_up, w_down, activation,
                 interpret):
    out = _forward(buf, block_eid, w_gate, w_up, w_down, activation,
                   interpret)
    return out, (buf, block_eid, w_gate, w_up, w_down)


def _grouped_bwd(activation, interpret, residuals, g):
    buf, block_eid, w_gate, w_up, w_down = residuals
    _, vjp = jax.vjp(
        lambda b, wg, wu, wd: grouped_ffn_reference(
            b, block_eid, wg, wu, wd, activation),
        buf, w_gate, w_up, w_down,
    )
    db, dwg, dwu, dwd = vjp(g)
    return db, None, dwg, dwu, dwd


_grouped.defvjp(_grouped_fwd, _grouped_bwd)

_grouped_jitted = jax.jit(_grouped, static_argnums=(5, 6))


def grouped_moe_ffn(buf, block_eid, params, *, activation: str,
                    interpret: Optional[bool] = None):
    """Fused grouped expert FFN over a block-padded sorted buffer.

    ``params`` is the ``models.layers.init_moe`` dict. ``interpret=None``
    resolves from the backend: the compiled kernel on TPU, the Pallas
    interpreter everywhere else.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    w_gate = params.get("w_gate", params["w_up"])
    return _grouped_jitted(buf, block_eid, w_gate, params["w_up"],
                           params["w_down"], activation, interpret)
