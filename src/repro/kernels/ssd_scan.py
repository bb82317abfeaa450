"""Pallas TPU kernel for the Mamba-2 SSD chunk recurrence.

TPU-native design:
  * grid (batch, heads, n_chunks) with the chunk dimension innermost and
    sequential; the running state h (head_dim x d_state) lives in VMEM
    scratch across chunk steps - the inter-chunk recurrence never touches
    HBM;
  * per step the kernel computes the intra-chunk (quadratic) term with two
    (chunk x chunk) MXU matmuls + the state in/out contributions, exactly
    mirroring ``repro.models.ssm.ssd_chunked``;
  * chunk length defaults to 64 and head_dim/d_state are zero-padded to
    lane multiples by the wrapper when needed.

Validated in interpret mode against ``ref.ssd_scan_ref``.
``interpret=None`` resolves from the backend: the compiled kernel on TPU,
the Pallas interpreter everywhere else.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    x_ref,  # (1, chunk, 1, P)
    dt_ref,  # (1, chunk, 1)
    a_ref,  # (1,)  decay rate for this head
    b_ref,  # (1, chunk, N)
    c_ref,  # (1, chunk, N)
    y_ref,  # (1, chunk, 1, P)
    hout_ref,  # (1, 1, P, N) final state
    h_ref,  # VMEM scratch (P, N)
    *,
    chunk: int,
    n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # (L, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # (L,)
    a = a_ref[0].astype(jnp.float32)  # scalar
    bm = b_ref[0].astype(jnp.float32)  # (L, N)
    cm = c_ref[0].astype(jnp.float32)  # (L, N)

    da = dt * a  # (L,) log-decay per step
    da_cum = jnp.cumsum(da)  # (L,)

    # intra-chunk: decay[i,j] = exp(da_cum[i] - da_cum[j]) for j <= i
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = lj <= li
    decay = jnp.where(tril, jnp.exp(da_cum[:, None] - da_cum[None, :]), 0.0)
    scores = (
        jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        * decay
    )  # (L, L)
    y_diag = jax.lax.dot_general(
        scores * dt[None, :], x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (L, P)

    # contribution of the incoming state
    state_decay = jnp.exp(da_cum)  # (L,)
    y_off = (
        jax.lax.dot_general(cm, h_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        * state_decay[:, None]
    )  # (L, P)
    y_ref[0, :, 0, :] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: h' = exp(sum da) h + sum_l exp(da_cum[-1]-da_cum[l]) dt_l x_l b_l^T
    decay_states = jnp.exp(da_cum[-1] - da_cum) * dt  # (L,)
    upd = jax.lax.dot_general(
        x * decay_states[:, None], bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (P, N)
    h_ref[...] = h_ref[...] * jnp.exp(da_cum[-1]) + upd

    @pl.when(ic == n_chunks - 1)
    def _emit():
        hout_ref[0, 0, :, :] = h_ref[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,  # (B, S, H, P) float32
    dt: jax.Array,  # (B, S, H)
    a: jax.Array,  # (H,)
    b: jax.Array,  # (B, S, N)
    c: jax.Array,  # (B, S, N)
    *,
    chunk: int = 64,
    interpret: Optional[bool] = None,
):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    n_chunks = x.shape[1] // chunk

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(bsz, h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, chunk, 1), lambda ib, ih, ic: (ib, ic, ih)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n_chunks * chunk, h, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, b, c)
    return y[:, :s], h_last
