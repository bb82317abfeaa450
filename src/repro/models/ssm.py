"""Mamba-2 (SSD, state-space duality) block [arXiv:2405.21060].

Chunked SSD algorithm: within-chunk quadratic attention-like term +
across-chunk recurrent state passing. Pure-jnp reference here; the Pallas
kernel in ``repro.kernels.ssd_scan`` implements the same chunk recurrence
with VMEM state carry and is validated against ``ssd_chunked``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Array = jax.Array


def segsum(x: Array) -> Array:
    """Stable 'segment sum': out[..., i, j] = sum_{j<k<=i} x[..., k], -inf for j>i.

    x: (..., T) -> (..., T, T) lower-triangular cumulative sums.
    """
    t = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None, :], x.shape + (t,)).swapaxes(-1, -2)
    mask = jnp.tril(jnp.ones((t, t), bool), k=-1)
    xx = jnp.where(mask, xx, 0)
    out = jnp.cumsum(xx, axis=-2)
    mask2 = jnp.tril(jnp.ones((t, t), bool), k=0)
    return jnp.where(mask2, out, -jnp.inf)


def ssd_chunked(
    x: Array,  # (B, S, H, P) inputs
    dt: Array,  # (B, S, H) positive step sizes
    a: Array,  # (H,) negative decay rates (A = -exp(a_log))
    b: Array,  # (B, S, N) input matrix (single group)
    c: Array,  # (B, S, N) output matrix
    chunk: int = 64,
    h0: Optional[Array] = None,  # (B, H, P, N) initial state
):
    """Chunked SSD. Returns (y: (B,S,H,P), h_final: (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk

    # chunked views: (B, nc, L, ...)
    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = b.reshape(bsz, nc, chunk, n)
    cc = c.reshape(bsz, nc, chunk, n)

    da = dtc * a[None, None, None, :]  # (B, nc, L, H) log-decay per step
    da_cum = jnp.cumsum(da, axis=2)  # within-chunk cumulative

    # 1) intra-chunk (diagonal block) output
    ss = segsum(da.transpose(0, 1, 3, 2))  # (B, nc, H, L, L)
    decay = jnp.exp(ss)
    scores = jnp.einsum("bzln,bzmn,bzhlm->bzhlm", cc, bc, decay)
    y_diag = jnp.einsum("bzhlm,bzmh,bzmhp->bzlhp", scores, dtc, xc)

    # 2) per-chunk final states
    decay_states = jnp.exp(da_cum[:, :, -1:, :] - da_cum)  # (B, nc, L, H)
    states = jnp.einsum("bzln,bzlh,bzlhp->bzhpn", bc, decay_states * dtc, xc)

    # 3) inter-chunk recurrence over chunk index
    chunk_decay = jnp.exp(da_cum[:, :, -1, :])  # (B, nc, H)

    def scan_fn(hprev, inp):
        st, dec = inp  # (B,H,P,N), (B,H)
        hnew = hprev * dec[:, :, None, None] + st
        return hnew, hprev

    h_init = (
        h0 if h0 is not None else jnp.zeros((bsz, h, p, n), x.dtype)
    )
    h_last, h_before = jax.lax.scan(
        scan_fn,
        h_init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    h_before = h_before.transpose(1, 0, 2, 3, 4)  # (B, nc, H, P, N) state entering chunk

    # 4) state -> output contribution
    state_decay = jnp.exp(da_cum)  # (B, nc, L, H)
    y_off = jnp.einsum("bzln,bzhpn,bzlh->bzlhp", cc, h_before, state_decay)

    y = (y_diag + y_off).reshape(bsz, nc * chunk, h, p)
    return y[:, :s], h_last


def ssd_decode_step(
    x: Array,  # (B, 1, H, P)
    dt: Array,  # (B, 1, H)
    a: Array,  # (H,)
    b: Array,  # (B, 1, N)
    c: Array,  # (B, 1, N)
    h: Array,  # (B, H, P, N)
):
    """Single recurrent step: h' = exp(dt*a) h + dt * x b^T ; y = h' c."""
    dec = jnp.exp(dt[:, 0, :] * a[None, :])  # (B, H)
    upd = jnp.einsum("bhp,bn->bhpn", x[:, 0] * dt[:, 0, :, None], b[:, 0])
    h_new = h * dec[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", h_new, c[:, 0])[:, None]
    return y, h_new


# ---------------------------------------------------------------------------
# full Mamba-2 block
# ---------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    sc = cfg.ssm
    di = sc.d_inner(d)
    nh = sc.num_heads(d)
    n = sc.d_state
    ks = jax.random.split(key, 5)
    d_in_proj = 2 * di + 2 * n + nh  # z, x, B, C, dt
    conv_dim = di + 2 * n
    return {
        "in_proj": (jax.random.normal(ks[0], (d, d_in_proj)) / math.sqrt(d)).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (sc.d_conv, conv_dim)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm_w": jnp.ones((di,), dtype),
        "out_proj": (jax.random.normal(ks[4], (di, d)) / math.sqrt(di)).astype(dtype),
    }


def causal_conv1d(x: Array, w: Array, bias: Array, state: Optional[Array] = None):
    """x: (B, S, C); w: (K, C) depthwise. Returns (y, new_state (B, K-1, C))."""
    k = w.shape[0]
    if state is not None:
        x_ext = jnp.concatenate([state, x], axis=1)
    else:
        x_ext = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    # depthwise conv as sum of shifted slices (K is tiny, 4)
    s = x.shape[1]
    y = sum(x_ext[:, i : i + s, :] * w[i][None, None, :] for i in range(k))
    y = y + bias[None, None, :]
    new_state = x_ext[:, -(k - 1) :, :] if k > 1 else None
    return jax.nn.silu(y), new_state


def mamba_apply(params, x: Array, cfg: ModelConfig, *, ssm_state=None, conv_state=None,
                use_pallas: bool = False):
    """Mamba-2 block. x: (B,S,D).

    Train/prefill: ssm_state/conv_state None -> chunked SSD, returns states.
    Decode: S==1 with states -> recurrent step.
    Returns (y, (new_ssm_state, new_conv_state)).
    """
    bsz, s, d = x.shape
    sc = cfg.ssm
    di = sc.d_inner(d)
    nh = sc.num_heads(d)
    n = sc.d_state

    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(x.dtype))
    z, xin, bmat, cmat, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1
    )
    conv_in = jnp.concatenate([xin, bmat, cmat], axis=-1)
    decode = ssm_state is not None and s == 1
    conv_out, new_conv = causal_conv1d(
        conv_in, params["conv_w"].astype(x.dtype), params["conv_b"].astype(x.dtype),
        state=conv_state.astype(x.dtype) if conv_state is not None else None,
    )
    if conv_state is not None and new_conv is not None:
        new_conv = new_conv.astype(conv_state.dtype)
    xin, bmat, cmat = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"][None, None, :])
    a = -jnp.exp(params["a_log"])  # (H,) negative

    xh = xin.reshape(bsz, s, nh, sc.head_dim)
    if decode:
        y, new_ssm = ssd_decode_step(
            xh.astype(jnp.float32), dt, a, bmat.astype(jnp.float32),
            cmat.astype(jnp.float32), ssm_state.astype(jnp.float32),
        )
    elif use_pallas:
        from repro.kernels import ops as kops

        y, new_ssm = kops.ssd_scan(
            xh.astype(jnp.float32), dt, a, bmat.astype(jnp.float32),
            cmat.astype(jnp.float32), chunk=sc.chunk,
        )
    else:
        y, new_ssm = ssd_chunked(
            xh.astype(jnp.float32), dt, a, bmat.astype(jnp.float32),
            cmat.astype(jnp.float32), chunk=sc.chunk,
            h0=ssm_state.astype(jnp.float32) if ssm_state is not None else None,
        )
    y = y + xh.astype(jnp.float32) * params["d_skip"][None, None, :, None]
    y = y.reshape(bsz, s, di).astype(x.dtype)
    # gated RMSNorm (Mamba-2)
    y = y * jax.nn.silu(z)
    dtv = y.dtype
    y32 = y.astype(jnp.float32)
    var = jnp.mean(y32 * y32, axis=-1, keepdims=True)
    y = (y32 * jax.lax.rsqrt(var + cfg.norm_eps)).astype(dtv) * params["norm_w"].astype(dtv)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(x.dtype))
    new_ssm = new_ssm.astype(jnp.float32)
    return out, (new_ssm, new_conv)
