"""Pallas TPU flash attention: causal, GQA, optional sliding window.

TPU-native design:
  * grid (batch, q_head, n_q, n_kv) with the kv dimension innermost and
    sequential: online-softmax state (m, l, acc) lives in VMEM scratch
    across kv steps - the HBM->VMEM working set per step is one
    (q_blk, hd) query tile plus one (kv_blk, hd) K/V tile each;
  * block shapes default to 128 - multiples of the 128-wide MXU;
  * GQA indexes the kv head as h // (H // KH) in the BlockSpec index map,
    so no repeated-KV copy ever exists in HBM;
  * fully-masked causal blocks are skipped with @pl.when (the grid still
    visits them, but no MXU work is issued).

Validated in interpret mode against ``ref.flash_attention_ref``.
``interpret=None`` resolves from the backend: the compiled kernel on TPU,
the Pallas interpreter everywhere else.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref,  # (1, q_blk, 1, hd), (1, kv_blk, 1, hd)
    o_ref,  # (1, q_blk, 1, hd)
    acc_ref, m_ref, l_ref,  # VMEM scratch: (q_blk, hd), (q_blk,), (q_blk,)
    *,
    scale: float,
    q_blk: int,
    kv_blk: int,
    n_kv: int,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    seq_kv: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level causal/window reachability (python-level only when static)
    qpos = q_offset + iq * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 0)
    kpos = ik * kv_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)
    needed = jnp.asarray(True)
    if causal:
        needed &= ik * kv_blk <= q_offset + iq * q_blk + q_blk - 1
    if window is not None:
        needed &= (ik + 1) * kv_blk - 1 > q_offset + iq * q_blk - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)  # (q_blk, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # (kv_blk, hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # (q_blk, kv_blk)
        ok = kpos < seq_kv
        if causal:
            ok &= kpos <= qpos
        if window is not None:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_blk", "kv_blk", "q_offset", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, KH, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_blk: int = 128,
    kv_blk: int = 128,
    q_offset: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, sq, h, hd = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g = h // kh
    q_blk = min(q_blk, sq)
    kv_blk = min(kv_blk, skv)
    n_q = -(-sq // q_blk)
    n_kv = -(-skv // kv_blk)
    pad_q = n_q * q_blk - sq
    pad_kv = n_kv * kv_blk - skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))

    kernel = functools.partial(
        _kernel,
        scale=1.0 / math.sqrt(hd),
        q_blk=q_blk,
        kv_blk=kv_blk,
        n_kv=n_kv,
        causal=causal,
        window=window,
        q_offset=q_offset,
        seq_kv=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, q_blk, 1, hd), lambda ib, ih, iq, ik: (ib, iq, ih, 0)),
            pl.BlockSpec((1, kv_blk, 1, hd), lambda ib, ih, iq, ik: (ib, ik, ih // g, 0)),
            pl.BlockSpec((1, kv_blk, 1, hd), lambda ib, ih, iq, ik: (ib, ik, ih // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_blk, 1, hd), lambda ib, ih, iq, ik: (ib, iq, ih, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_q * q_blk, h, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, hd), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
