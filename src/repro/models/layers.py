"""Core transformer layers: RMSNorm, RoPE, GQA attention, MLPs, MoE.

Everything is pure-functional: ``init_*`` returns a param pytree,
``*_apply``-style functions consume it. Compute runs in ``cfg`` activation
dtype (bf16 by default) with f32 softmax/norm accumulation.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Array = jax.Array

# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------


def rms_norm(x: Array, weight: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * weight.astype(dt)


def init_dense(key, d_in: int, d_out: int, dtype=jnp.float32, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def activation_fn(name: str):
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "silu":
        return jax.nn.silu
    raise KeyError(name)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_angles(positions: Array, head_dim: int, theta: float) -> tuple[Array, Array]:
    """positions: (..., S) int -> cos/sin of shape (..., S, head_dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (B, S, H, hd); cos/sin: (S, hd//2) or (B, S, hd//2)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:  # (S, hd/2) -> broadcast over batch and heads
        cos_, sin_ = cos[None, :, None, :], sin[None, :, None, :]
    else:  # (B, S, hd/2)
        cos_, sin_ = cos[:, :, None, :], sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos_ - x2 * sin_, x1 * sin_ + x2 * cos_], axis=-1)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], d, h * hd, dtype),
        "wk": init_dense(ks[1], d, kh * hd, dtype),
        "wv": init_dense(ks[2], d, kh * hd, dtype),
        "wo": init_dense(ks[3], h * hd, d, dtype, scale=1.0 / math.sqrt(h * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kh * hd,), dtype)
        p["bv"] = jnp.zeros((kh * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _mask_bias(qpos: Array, kpos: Array, window: Optional[int]) -> Array:
    """(Sq, Skv) additive f32 bias: 0 allowed, -inf disallowed."""
    ok = kpos[None, :] <= qpos[:, None]
    if window is not None:
        ok &= kpos[None, :] > (qpos[:, None] - window)
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def _repeat_kv(k: Array, groups: int) -> Array:
    if groups == 1:
        return k
    b, s, kh, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, groups, hd)).reshape(
        b, s, kh * groups, hd
    )


def dense_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    q_offset,
    window: Optional[int] = None,
    causal: bool = True,
) -> Array:
    """Reference attention; materializes (Sq, Skv) scores. q:(B,Sq,H,hd)."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores *= 1.0 / math.sqrt(hd)
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(k.shape[1])
    if causal:
        scores = scores + _mask_bias(qpos, kpos, window)[None, None]
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


def chunked_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    q_offset: int = 0,
    window: Optional[int] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> Array:
    """Flash-style online-softmax attention in pure JAX (no SqxSkv temp).

    Outer scan over q chunks, inner scan over kv chunks; peak temporary is
    (B, H, q_chunk, kv_chunk). Causal + optional sliding window.
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    kh = k.shape[2]
    g = h // kh
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    n_q = -(-sq // q_chunk)
    n_kv = -(-skv // kv_chunk)
    pad_q = n_q * q_chunk - sq
    pad_kv = n_kv * kv_chunk - skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))

    # (n, B, chunk, heads, hd) layouts for scan
    qs = q.reshape(b, n_q, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    ks = k.reshape(b, n_kv, kv_chunk, kh, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, n_kv, kv_chunk, kh, hd).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / math.sqrt(hd)

    def q_body(_, qc_i):
        qc, qi = qc_i
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_body(carry, kc_vc_i):
            acc, m, l = carry
            kc, vc, ki = kc_vc_i
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            kc_r = _repeat_kv(kc, g)
            vc_r = _repeat_kv(vc, g)
            s = (
                jnp.einsum("bqhd,bkhd->bhqk", qc, kc_r, preferred_element_type=jnp.float32)
                * scale
            )
            bias = _mask_bias(qpos, kpos, window)
            # mask out kv padding
            bias = jnp.where((kpos < skv)[None, :], bias, -jnp.inf)
            s = s + bias[None, None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            # fully-masked blocks: keep p/corr at exactly 0, never exp(-inf+inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(vc_r.dtype), vc_r
            ).astype(jnp.float32)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, h, q_chunk, hd), jnp.float32)
        m0 = jnp.full((b, h, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            jax.checkpoint(kv_body), (acc0, m0, l0), (ks, vs, jnp.arange(n_kv))
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 2, 1, 3)  # (B, q_chunk, H, hd)

    _, outs = jax.lax.scan(jax.checkpoint(q_body), None, (qs, jnp.arange(n_q)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, n_q * q_chunk, h, hd)
    return out[:, :sq].astype(q.dtype)


def kv_write(cache: Array, fresh: Array, index, layer=None) -> Array:
    """Write the fresh K or V rows ``fresh`` (B, s, KH, hd) into ``cache``.

    ``cache`` is one layer's (B, C, KH, hd), or, with ``layer`` = r, the
    (R, B, C, KH, hd) stack a layer loop carries: layer r is written in
    place and the whole stack returned, never sliced out and restacked.
    ``index`` is a scalar position every row shares, or a (B,) vector of
    PER-ROW positions: row ``b`` writes at its OWN ``index[b]``, which lets
    one compiled decode step serve a continuous batch of slots sitting at
    different sequence positions (the serving engine's per-slot KV rings).
    As in ``dynamic_update_slice``, a start past ``C - s`` is clamped.
    """
    if layer is None:
        return kv_write(cache[None], fresh, index, 0)[0]
    fresh = fresh.astype(cache.dtype)
    with jax.named_scope("model.kv_write"):
        if jnp.ndim(index) == 0:
            return jax.lax.dynamic_update_slice(cache, fresh[None],
                                                (layer, 0, index, 0, 0))
        # one in-place update per row: on a TPU v5e, 8 decode steps of
        # 8 slots ran 9% faster than with a scatter, which that compiler
        # expands into a loop over the rows
        for b in range(fresh.shape[0]):
            cache = jax.lax.dynamic_update_slice(
                cache, fresh[b][None, None], (layer, b, index[b], 0, 0))
        return cache


def attention_apply(
    params,
    x: Array,
    cfg: ModelConfig,
    *,
    positions: Array,
    kv_cache=None,
    cache_index=None,
    layer=None,
    impl: str = "auto",
):
    """Self-attention with GQA + RoPE (+ per-head QK-norm if configured).

    positions: (S,) absolute positions of the inputs, or (B, S) per-row
    positions when ``cache_index`` is a vector.
    kv_cache: optional dict {k:(B,C,KH,hd), v:(B,C,KH,hd)} - decode mode.
    layer: when given, ``kv_cache`` holds the (R,B,C,KH,hd) stacks a layer
    loop carries; the fresh K/V are written into layer ``layer`` in place,
    attention reads that layer, and ``new_cache`` is the whole stack.
    cache_index: scalar number of valid entries already in the cache, or a
    (B,) vector of PER-ROW entry counts (slot-indexed decode: every batch
    row writes its fresh K/V at its own position and masks its own
    history; see :func:`kv_write`).
    Returns (out, new_cache).
    """
    b, s, d = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,de->bse", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,de->bse", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kh, hd)
    v = v.reshape(b, s, kh, hd)
    if cfg.qk_norm:
        # every path (training, prefill, cached decode) normalises the
        # fresh q and k here, so the cache holds normalised, rotated keys
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    new_cache = None
    vec_idx = cache_index is not None and jnp.ndim(cache_index) == 1
    if kv_cache is not None:
        cache_len = kv_cache["k"].shape[-3]
        # ring-buffer cache for sliding-window decode (1 token)
        ring = (cfg.attention_window is not None
                and cache_len == cfg.attention_window and s == 1)
        # write first, then read layer ``layer`` of the updated stack
        at = cache_index % cache_len if ring else cache_index
        new_cache = {n: kv_write(kv_cache[n], fresh, at, layer)
                     for n, fresh in (("k", k), ("v", v))}
        ck, cv = (c if layer is None
                  else jax.lax.dynamic_index_in_dim(c, layer, 0, False)
                  for c in (new_cache["k"], new_cache["v"]))
        if ring:
            t = cache_index  # absolute position(s) of the new token
            # entry i now holds absolute position t - ((t - i) mod L), which is
            # always within the window; it is valid iff it is >= 0.
            idx = jnp.arange(cache_len)
            if vec_idx:
                abs_pos = t[:, None] - jnp.mod(t[:, None] - idx[None, :], cache_len)
                kpos_bias = jnp.where(abs_pos >= 0, 0.0, -jnp.inf)[:, None, None, :]
            else:
                abs_pos = t - jnp.mod(t - idx, cache_len)
                kpos_bias = jnp.where(abs_pos >= 0, 0.0, -jnp.inf)[None, None, None, :]
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk",
                q,
                _repeat_kv(ck, h // kh),
                preferred_element_type=jnp.float32,
            ) / math.sqrt(hd)
            scores = scores + kpos_bias
            w = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), _repeat_kv(cv, h // kh))
        else:
            kpos = jnp.arange(cache_len)
            qpos = positions  # (s,) absolute, or (B, s) per-row
            ok = kpos <= qpos[..., None]
            if vec_idx:
                ok &= kpos < (cache_index[:, None, None] + s)
            else:
                ok &= kpos[None, :] < (cache_index + s)
            if cfg.attention_window is not None:
                ok &= kpos > (qpos[..., None] - cfg.attention_window)
            bias = jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk",
                q,
                _repeat_kv(ck, h // kh),
                preferred_element_type=jnp.float32,
            ) / math.sqrt(hd)
            scores = scores + (bias[:, None] if vec_idx else bias[None, None])
            w = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum(
                "bhqk,bkhd->bqhd", w.astype(v.dtype), _repeat_kv(cv, h // kh)
            )
    else:
        use_chunked = impl == "chunked" or (impl == "auto" and s > 2048)
        if impl == "pallas":
            from repro.kernels import ops as kops

            out = kops.flash_attention(
                q, k, v, causal=True, window=cfg.attention_window
            )
        elif use_chunked:
            out = chunked_attention(q, k, v, q_offset=0, window=cfg.attention_window)
        else:
            out = dense_attention(q, k, v, q_offset=0, window=cfg.attention_window)

    out = out.reshape(b, s, h * hd).astype(x.dtype)  # cache dtype may differ
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, activation: str, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if activation == "swiglu":
        return {
            "w_gate": init_dense(ks[0], d_model, d_ff, dtype),
            "w_up": init_dense(ks[1], d_model, d_ff, dtype),
            "w_down": init_dense(ks[2], d_ff, d_model, dtype, scale=1.0 / math.sqrt(d_ff)),
        }
    return {
        "w_up": init_dense(ks[0], d_model, d_ff, dtype),
        "w_down": init_dense(ks[1], d_ff, d_model, dtype, scale=1.0 / math.sqrt(d_ff)),
    }


def mlp_apply(params, x: Array, activation: str) -> Array:
    if activation == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(x.dtype))
        hcurr = jax.nn.silu(g) * u
    else:
        u = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(x.dtype))
        hcurr = activation_fn(activation)(u)
    return jnp.einsum("bsf,fd->bsd", hcurr, params["w_down"].astype(x.dtype))


def mlp_block(norm_w: Array, params, x: Array, activation: str,
              eps: float = 1e-6) -> Array:
    """Reference residual MLP half-block: ``x + mlp(rms_norm(x))``.

    This is the exact computation the fused Pallas stage kernel
    (``repro.kernels.stage_block``) performs in one VMEM-resident pass;
    the kernel's custom VJP differentiates THIS function, so the two are
    gradient-identical by construction.
    """
    return x + mlp_apply(params, rms_norm(x, norm_w, eps), activation)


# ---------------------------------------------------------------------------
# MoE (top-k routing, dropless sort-based dispatch, grouped expert FFN)
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    """The router scores all ``num_experts``; the expert stacks hold only
    this layer's share (``MoEConfig.held`` of them)."""
    m = cfg.moe
    ks = jax.random.split(key, 4)
    d, f, e, held = cfg.d_model, m.expert_d_ff, m.num_experts, m.held
    p = {
        "router": init_dense(ks[0], d, e, jnp.float32),
        "w_up": (jax.random.normal(ks[2], (held, d, f)) / math.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (held, f, d)) / math.sqrt(f)).astype(dtype),
    }
    if cfg.activation == "swiglu":
        p["w_gate"] = (jax.random.normal(ks[1], (held, d, f)) / math.sqrt(d)).astype(dtype)
    return p


def _moe_route(params, xt: Array, cfg: ModelConfig):
    """Shared token routing for the dropless path and its dense reference.

    xt: (T, D) flattened tokens. Returns (gates (T, k) f32 renormalized
    over the k choices, expert_ids (T, k) int32 over ALL ``num_experts``,
    aux scalar). Identical code on both sides is what makes the
    dropless-vs-dense parity BITWISE rather than approximate.

    ``aux`` is HF's ``load_balancing_loss_func`` over all k choices, ``E *
    sum_e (tokens that chose e among their k, over T) * (mean router
    probability of e)``, times ``router_aux_weight`` and over the model's
    MoE layers, so that the layers' sum is the weighted mean. Every expert
    share of a layer computes it alike from the whole router.
    """
    m = cfg.moe
    e, k = m.num_experts, m.top_k
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    f_e = jnp.mean(jax.nn.one_hot(expert_ids, e, dtype=jnp.float32).sum(1),
                   axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = (e * jnp.sum(f_e * p_e) * m.router_aux_weight
           / max(cfg.num_moe_layers, 1))
    return gate_vals, expert_ids, aux


def _held_choices(ids: Array, cfg: ModelConfig):
    """(T, k) expert ids -> (T, k) index into this layer's held experts,
    and whether each choice is held here."""
    local = ids - cfg.moe.expert_start
    return local, (local >= 0) & (local < cfg.moe.held)


def _moe_combine(out_choices: Array, gates: Array, dtype) -> Array:
    """(T, k, D) per-choice expert outputs + (T, k) gates -> (T, D).

    The k-summation runs through ONE einsum on both the dropless and the
    dense-reference side, so the combine order is identical (a scatter-add
    combine would not be)."""
    return jnp.einsum("tkd,tk->td", out_choices, gates.astype(dtype))


def moe_apply_dense(params, x: Array, cfg: ModelConfig):
    """Dense per-expert reference: EVERY held expert FFN over EVERY token.

    x: (B, S, D) -> (y, aux). O(T * E) FFN rows - the bitwise ground truth
    the dropless dispatch is parity-pinned against, never a production
    path. Written as a python loop over experts so each expert's rows go
    through a plain (T, D) @ (D, F) gemm. Choices of experts held
    elsewhere (``MoEConfig.held`` < ``num_experts``) add nothing.
    """
    b, s, d = x.shape
    m = cfg.moe
    xt = x.reshape(b * s, d)
    gates, ids, aux = _moe_route(params, xt, cfg)
    local, mine = _held_choices(ids, cfg)
    swiglu = cfg.activation == "swiglu"
    per_expert = []
    for j in range(m.held):
        wu = params["w_up"][j].astype(x.dtype)
        wd = params["w_down"][j].astype(x.dtype)
        if swiglu:
            wg = params["w_gate"][j].astype(x.dtype)
            g = jnp.einsum("td,df->tf", xt, wg,
                           preferred_element_type=jnp.float32).astype(x.dtype)
            u = jnp.einsum("td,df->tf", xt, wu,
                           preferred_element_type=jnp.float32).astype(x.dtype)
            h = jax.nn.silu(g) * u
        else:
            u = jnp.einsum("td,df->tf", xt, wu,
                           preferred_element_type=jnp.float32).astype(x.dtype)
            h = activation_fn(cfg.activation)(u)
        per_expert.append(
            jnp.einsum("tf,fd->td", h, wd,
                       preferred_element_type=jnp.float32).astype(x.dtype))
    stacked = jnp.stack(per_expert)  # (E_held, T, D)
    t = b * s
    got = stacked[jnp.clip(local, 0, m.held - 1), jnp.arange(t)[:, None]]
    got = jnp.where(mine[..., None], got, 0)  # (T, k, D)
    y = _moe_combine(got, gates, x.dtype)
    return y.reshape(b, s, d), aux


@jax.custom_vjp
def _permute_rows(x: Array, perm: Array, inv: Array) -> Array:
    """``x[perm]`` for a permutation ``perm`` of x's rows whose inverse is
    ``inv``: the backward pass is the gather ``g[inv]``, not the
    scatter-add a general gather transposes to."""
    return x[perm]


def _permute_rows_fwd(x, perm, inv):
    return x[perm], (perm, inv)


def _permute_rows_bwd(res, g):
    perm, inv = res
    return g[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def moe_apply_dropless(params, x: Array, cfg: ModelConfig, *,
                       impl: str = "auto", block_size: int = 128,
                       interpret=None):
    """Dropless MoE dispatch; x: (B, S, D) -> (y, aux). See
    :func:`moe_dropless`, which also returns the routed rows."""
    y, aux, _ = moe_dropless(params, x, cfg, impl=impl, block_size=block_size,
                             interpret=interpret)
    return y, aux


def moe_dropless(params, x: Array, cfg: ModelConfig, *, impl: str = "auto",
                 block_size: int = 128, interpret=None):
    """Dropless MoE dispatch: sort-based token grouping + grouped matmul.

    Every routed (token, choice) whose expert is held here is computed -
    no capacity buffer, no token dropping, so the output of a token is
    independent of which other tokens are routed with it (a prefill and a
    one-token decode step agree). The router scores all ``num_experts``;
    choices of experts held elsewhere (``MoEConfig.held`` < ``num_experts``)
    sort after the held ones and add nothing, so the shares of a layer sum
    to the whole layer.

    x: (B, S, D) -> (y, aux, rows), ``rows`` (held,) int32 the routed rows
    of each held expert. Stable-argsort the (T*k) flat expert ids, gather
    tokens into expert-contiguous rows, run the expert FFN grouped, then
    gather back through the inverse permutation and combine with one
    einsum (order-preserving, see ``_moe_combine``).

    ``impl="auto"`` picks from the backend: on a TPU ``"ragged"``, the
    ``lax.ragged_dot`` grouped matmul over the sorted rows (each held
    expert's weights read once; the rows of other shares' choices lie past
    the groups and are not computed), elsewhere ``"reference"``.
    ``"reference"`` and ``"pallas"`` share one padded layout: per-expert
    regions padded up to ``block_size`` rows (a STATIC
    ``T*k + E*(block_size-1)`` row bound, so the whole dispatch jits with
    fixed shapes; padding rows are zero and never gathered back).
    ``"reference"`` runs the jittable
    ``kernels.moe_dispatch.grouped_ffn_reference`` batched einsum (the
    CPU path); ``"pallas"`` runs the fused ``grouped_moe_ffn`` Pallas
    kernel over the same blocks. Both are bitwise-identical to
    ``moe_apply_dense`` on CPU (pinned by ``tests/test_moe_dropless.py``;
    ``lax.ragged_dot`` is not: its gemm blocking drifts ~2e-6 from the
    plain per-expert gemm).

    Device scopes: ``model.moe`` holding ``model.moe.route`` (router,
    top-k, sort and dispatch), ``model.moe.ffn`` (the grouped matmul) and
    ``model.moe.combine``.
    """
    from repro.kernels.moe_dispatch import (
        grouped_ffn_ragged, grouped_ffn_reference, grouped_moe_ffn,
    )

    m = cfg.moe
    b, s, d = x.shape
    k, held = m.top_k, m.held
    t = b * s
    if impl == "auto":
        impl = "ragged" if jax.default_backend() == "tpu" else "reference"

    def experts(w, xt, gates, mine, flat, order, inv, counts):
        """Dispatch, grouped FFN and combine, given the routing."""
        with jax.named_scope("model.moe.route"):
            if impl == "ragged":
                xs = _permute_rows(jnp.repeat(xt, k, axis=0), order, inv)
            else:
                sorted_eids = flat[order]
                blk = block_size
                padded = ((counts + blk - 1) // blk) * blk       # (held,)
                starts = jnp.cumsum(padded) - padded
                excl = jnp.cumsum(counts) - counts
                pos_in_expert = jnp.arange(t * k) - excl[sorted_eids]
                dest = starts[sorted_eids] + pos_in_expert          # unique rows
                p_rows = -(-(t * k + held * (blk - 1)) // blk) * blk  # static
                # other shares' rows are dropped
                dest = jnp.where(sorted_eids < held, dest, p_rows)
                pbuf = jnp.zeros((p_rows, d), x.dtype).at[dest].set(
                    xt[order // k], mode="drop")
                block_eid = jnp.minimum(
                    jnp.searchsorted(jnp.cumsum(padded),
                                     jnp.arange(p_rows // blk) * blk,
                                     side="right"),
                    held - 1).astype(jnp.int32)

        with jax.named_scope("model.moe.ffn"):
            if impl == "ragged":
                out_sorted = grouped_ffn_ragged(
                    xs, counts, w.get("w_gate"), w["w_up"], w["w_down"],
                    cfg.activation)
            elif impl == "reference":
                out_p = grouped_ffn_reference(
                    pbuf, block_eid, w.get("w_gate"), w["w_up"],
                    w["w_down"], cfg.activation)
            elif impl == "pallas":
                out_p = grouped_moe_ffn(pbuf, block_eid, w,
                                        activation=cfg.activation,
                                        interpret=interpret)
            else:
                raise ValueError(f"unknown dropless impl {impl!r}")

        with jax.named_scope("model.moe.combine"):
            if impl == "ragged":
                got = _permute_rows(out_sorted, inv, order)
            else:
                got = out_p[dest][inv]
            got = jnp.where(mine[..., None], got.reshape(t, k, d), 0)
            return _moe_combine(got, gates, x.dtype)

    with jax.named_scope("model.moe"):
        with jax.named_scope("model.moe.route"):
            xt = x.reshape(t, d)
            gates, ids, aux = _moe_route(params, xt, cfg)
            local, mine = _held_choices(ids, cfg)
            # (T*k,) token-major; other shares' choices sort last
            flat = jnp.where(mine, local, held).reshape(-1)
            order = jnp.argsort(flat)           # stable: ties keep token order
            inv = jnp.argsort(order)            # flat choice -> sorted row
            # a sentinel id (held) one-hots to a zero row
            counts = jnp.sum(jax.nn.one_hot(flat, held, dtype=jnp.int32), 0)
        # the dispatched rows are k times the tokens: the backward pass
        # recomputes them from the tokens and the routing, not keeps them
        w = {n: params[n] for n in ("w_gate", "w_up", "w_down") if n in params}
        y = jax.checkpoint(experts)(w, xt, gates, mine, flat, order, inv,
                                    counts)
    return y.reshape(b, s, d), aux, counts
