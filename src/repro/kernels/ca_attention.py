"""Pallas fused history cross-attention for the CA actor (paper Eq. 24).

``agents.attention.cross_attention`` scores a full ``(batch, I+1, C)``
query block - the current-state query s(n) stacked on the I history
queries - but the actor consumes ONLY the current-state row of the
attended output. The reference therefore pays ``(I+1) x I`` score work
(plus the W_Q H projection of every history row) for one useful row.

This kernel fuses the useful part into a single VMEM-resident pass per
batch tile:

  * grid ``(n_b,)`` over batch tiles of ``blk`` rows; the projection
    weights ride along whole (they are tiny: pair_dim x C);
  * per tile: one ``(blk, obs_dim) @ (obs_dim, C)`` query projection,
    the K/V projections of the ``(blk*I, pair_dim)`` history block, the
    masked ``(blk, I)`` score row for the current-state query only, a
    numerically-stable softmax, and the weighted V reduction - no
    ``(I+1, I)`` score matrix, no W_Q H projection, no HBM round-trip
    between the five ops;
  * masking uses ``jnp.finfo(dtype).min`` (not a ``-1e9`` literal), so
    the kernel stays correct when scores are bf16/fp16;
  * rows with no valid history attend to nothing and emit zeros, exactly
    like the reference's ``any_valid`` guard.

``interpret=None`` (the default) resolves from the backend: the compiled
kernel on TPU, the Pallas interpreter elsewhere - which is how
``tests/test_kernels.py`` checks parity against
``agents.attention.cross_attention`` on the CPU.
"""
from __future__ import annotations

import functools
import math

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(obs_ref, hist_ref, mask_ref, wqs_ref, wk_ref, wv_ref, out_ref,
            *, scale: float):
    obs = obs_ref[...]  # (blk, obs_dim)
    hist = hist_ref[...]  # (blk, I, pair_dim)
    blk, i, pair_dim = hist.shape

    q = jnp.dot(obs, wqs_ref[...], preferred_element_type=jnp.float32)
    h2 = hist.reshape(blk * i, pair_dim)
    k = jnp.dot(h2, wk_ref[...], preferred_element_type=jnp.float32)
    v = jnp.dot(h2, wv_ref[...], preferred_element_type=jnp.float32)
    k = k.reshape(blk, i, -1)
    v = v.reshape(blk, i, -1)

    # current-state query row only: (blk, I) scores on the VPU (I is tiny,
    # so an MXU batched matmul would waste the systolic array)
    s = (q[:, None, :] * k).sum(axis=-1) * scale
    valid = mask_ref[...] > 0  # (blk, I)
    s = jnp.where(valid, s, jnp.finfo(s.dtype).min)
    s = s - s.max(axis=-1, keepdims=True)
    e = jnp.exp(s)
    w = e / e.sum(axis=-1, keepdims=True)
    att = (w[:, :, None] * v).sum(axis=1)  # (blk, C)
    att = jnp.where(valid.any(axis=-1)[:, None], att, 0.0)
    out_ref[...] = att.astype(out_ref.dtype)


def _ca_forward(params, obs: jax.Array, history: jax.Array,
                hist_mask: jax.Array, blk: int, interpret: bool) -> jax.Array:
    b, obs_dim = obs.shape
    i, pair_dim = history.shape[1], history.shape[2]
    c = params["wk"].shape[-1]
    blk = min(blk, b)
    n_b = -(-b // blk)
    pad = n_b * blk - b
    if pad:
        obs = jnp.pad(obs, ((0, pad), (0, 0)))
        history = jnp.pad(history, ((0, pad), (0, 0), (0, 0)))
        # padded rows carry an all-invalid mask and emit zeros
        hist_mask = jnp.pad(hist_mask, ((0, pad), (0, 0)))

    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(c))
    s_prime = pl.pallas_call(
        kernel,
        grid=(n_b,),
        in_specs=[
            pl.BlockSpec((blk, obs_dim), lambda ib: (ib, 0)),
            pl.BlockSpec((blk, i, pair_dim), lambda ib: (ib, 0, 0)),
            pl.BlockSpec((blk, i), lambda ib: (ib, 0)),
            pl.BlockSpec((obs_dim, c), lambda ib: (0, 0)),
            pl.BlockSpec((pair_dim, c), lambda ib: (0, 0)),
            pl.BlockSpec((pair_dim, c), lambda ib: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk, c), lambda ib: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((n_b * blk, c), obs.dtype),
        interpret=interpret,
    )(obs, history, hist_mask, params["wq_s"], params["wk"], params["wv"])
    return jnp.concatenate([obs[:b], s_prime[:b]], axis=-1)


# Training reaches this kernel through the actor loss, and pallas_call has
# no built-in transpose rule - so the backward pass is the jax AD of the
# mathematically-identical slim reference (custom-VJP kernel pattern).
# ``wq_h`` receives its exact zero cotangent like every other unused leaf.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ca(params, obs, history, hist_mask, blk, interpret):
    return _ca_forward(params, obs, history, hist_mask, blk, interpret)


def _ca_fwd(params, obs, history, hist_mask, blk, interpret):
    out = _ca_forward(params, obs, history, hist_mask, blk, interpret)
    return out, (params, obs, history, hist_mask)


def _ca_bwd(blk, interpret, residuals, g):
    from repro.core.agents.attention import cross_attention_slim

    params, obs, history, hist_mask = residuals
    _, vjp = jax.vjp(
        lambda p, o, h: cross_attention_slim(p, o, h, hist_mask),
        params, obs, history,
    )
    dp, do, dh = vjp(g)
    return dp, do, dh, jnp.zeros_like(hist_mask)


_ca.defvjp(_ca_fwd, _ca_bwd)


_ca_jitted = jax.jit(_ca, static_argnums=(4, 5))


def ca_attention(params, obs: jax.Array, history: jax.Array,
                 hist_mask: jax.Array, *, blk: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Fused masked history cross-attention (batched call sites only).

    ``params``: the ``agents.attention.init_cross_attention`` dict
    (``wq_h`` is unused - only the current-state query row survives to
    the output). ``obs`` (B, obs_dim), ``history`` (B, I, pair_dim)
    newest-last, ``hist_mask`` (B, I) with 1 = valid pair. Returns
    ``(B, obs_dim + C)``: the observation concatenated with the attended
    summary, matching ``cross_attention``'s output contract.
    Differentiable: the backward pass runs the slim reference's VJP.

    ``interpret=None`` (the default, and what ``SACConfig.ca_impl``'s
    call sites use) resolves from the backend: the compiled kernel on
    TPU, the Pallas interpreter everywhere else.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ca_jitted(params, obs, history, hist_mask, blk, interpret)
