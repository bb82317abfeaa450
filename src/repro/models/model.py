"""Decoder LM assembly: embedding -> scan over layer groups -> logits.

Architecture-generic: the per-layer ``signature`` (block kind A/M, MoE flag,
MLP presence) is derived from the config; layers are grouped into the
smallest repeating period so ``jax.lax.scan`` keeps compile time O(period),
not O(depth).

KV/SSM caches are stacked per slot (leading dim = repeats) and carried
through the layer loop: each layer writes its new K/V rows (or SSM state)
into the stack in place at its own index, so a decode step writes only
the new positions and never slices out or restacks a layer's cache.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.frontends import init_frontend, frontend_apply

Array = jax.Array


# ---------------------------------------------------------------------------
# layer signatures and period grouping
# ---------------------------------------------------------------------------


def signature(cfg: ModelConfig):
    """Per-layer (kind, is_moe, has_mlp)."""
    sig = []
    for i in range(cfg.num_layers):
        kind = cfg.pattern[i]
        is_moe = cfg.is_moe_block(i) and (kind == "A" or cfg.arch_type == "hybrid")
        has_mlp = kind == "A" or cfg.arch_type == "hybrid"
        sig.append((kind, is_moe, has_mlp))
    return tuple(sig)


def find_period(sig) -> int:
    n = len(sig)
    for p in range(1, n + 1):
        if n % p == 0 and all(sig[i] == sig[i % p] for i in range(n)):
            return p
    return n


# ---------------------------------------------------------------------------
# per-slot block init / apply
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, slot_sig, dtype=jnp.float32):
    kind, is_moe, has_mlp = slot_sig
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"norm1": jnp.ones((cfg.d_model,), dtype)}
    if kind == "A":
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
    else:
        p["mamba"] = S.init_mamba(ks[0], cfg, dtype)
    if has_mlp:
        p["norm2"] = jnp.ones((cfg.d_model,), dtype)
        if is_moe:
            p["moe"] = L.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    return p


def block_apply(
    p,
    x: Array,
    cfg: ModelConfig,
    slot_sig,
    *,
    positions,
    cache=None,
    cache_index=None,
    layer=None,
    impl: str = "auto",
):
    """One residual block. Returns (x, new_cache, stats).

    ``cache`` is this layer's cache dict, or, with ``layer`` = r, its
    slot's stacked dict as the layer loop carries it: layer r is updated
    in place and ``new_cache`` is the whole stack. ``stats["aux"]`` is the
    block's share of the auxiliary loss (the MoE router's; 0 elsewhere);
    an MoE block's ``stats["moe_rows"]`` (held,) counts the rows routed to
    each expert it holds.
    """
    kind, is_moe, has_mlp = slot_sig
    stats = {"aux": jnp.zeros((), jnp.float32)}
    # "pallas_stage" (the split executor's PipelineConfig.stage_impl knob)
    # fuses the residual MLP half-block; the attention/mamba half keeps the
    # default routing.
    half_impl = "auto" if impl == "pallas_stage" else impl
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind == "A":
        out, new_kv = L.attention_apply(
            p["attn"], h, cfg, positions=positions,
            kv_cache=None if cache is None else {"k": cache["k"], "v": cache["v"]},
            cache_index=cache_index, layer=layer, impl=half_impl,
        )
        new_cache = {} if new_kv is None else new_kv
    else:
        state = cache
        if cache is not None and layer is not None:
            state = {n: jax.lax.dynamic_index_in_dim(c, layer, 0, False)
                     for n, c in cache.items()}
        out, (new_ssm, new_conv) = S.mamba_apply(
            p["mamba"], h, cfg,
            ssm_state=None if state is None else state["ssm"],
            conv_state=None if state is None else state["conv"],
            use_pallas=(half_impl == "pallas"),
        )
        new_cache = {}
        if cache is not None:
            new_cache = {"ssm": new_ssm, "conv": new_conv}
            if layer is not None:
                new_cache = {n: jax.lax.dynamic_update_index_in_dim(
                    cache[n], c, layer, 0) for n, c in new_cache.items()}
    x = x + out
    if has_mlp:
        if is_moe:
            h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
            y, aux, rows = L.moe_dropless(p["moe"], h2, cfg)
            stats = {"aux": aux, "moe_rows": rows}
            x = x + y
        elif impl == "pallas_stage":
            from repro.kernels.stage_block import stage_mlp_block

            x = stage_mlp_block(p["norm2"], p["mlp"], x,
                                activation=cfg.activation, eps=cfg.norm_eps)
        else:
            x = L.mlp_block(p["norm2"], p["mlp"], x, cfg.activation,
                            cfg.norm_eps)
    return x, new_cache, stats


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    sig = signature(cfg)
    period = find_period(sig)
    repeats = cfg.num_layers // period
    keys = jax.random.split(key, period + 3)
    slots = []
    for si in range(period):
        slot_keys = jax.random.split(keys[si], repeats)
        slots.append(jax.vmap(lambda k: init_block(k, cfg, sig[si], dtype))(slot_keys))
    params = {
        "embed": (jax.random.normal(keys[-1], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(
            dtype
        ),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "slots": tuple(slots),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[-2], (cfg.d_model, cfg.vocab_size))
            / math.sqrt(cfg.d_model)
        ).astype(dtype)
    if cfg.frontend != "none":
        params["frontend"] = init_frontend(keys[-3], cfg, dtype)
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16):
    """Per-slot stacked caches (leading dim = repeats)."""
    sig = signature(cfg)
    period = find_period(sig)
    repeats = cfg.num_layers // period
    kv_len = (
        min(cache_len, cfg.attention_window)
        if cfg.attention_window is not None
        else cache_len
    )
    caches = []
    for si in range(period):
        kind, _, _ = sig[si]
        if kind == "A":
            shape = (repeats, batch, kv_len, cfg.num_kv_heads, cfg.head_dim)
            caches.append({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)})
        else:
            sc = cfg.ssm
            di = sc.d_inner(cfg.d_model)
            nh = sc.num_heads(cfg.d_model)
            caches.append(
                {
                    "ssm": jnp.zeros(
                        (repeats, batch, nh, sc.head_dim, sc.d_state), jnp.float32
                    ),
                    "conv": jnp.zeros(
                        (repeats, batch, sc.d_conv - 1, di + 2 * sc.d_state), dtype
                    ),
                }
            )
    return tuple(caches)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(
    params,
    tokens: Array,
    cfg: ModelConfig,
    *,
    caches=None,
    cache_index=None,
    frontend_feats: Optional[Array] = None,
    impl: str = "auto",
    remat: bool = False,
    compute_dtype=jnp.bfloat16,
    last: Optional[Array] = None,
):
    """tokens: (B, S) int32. Returns (logits, new_caches, aux_loss).

    frontend_feats: (B, F, d_frontend) stub modality embeddings, prepended.
    last: optional (B,) int32 positions; then only row ``last[b]`` of each
    sequence reaches the final norm and the head, and the logits are
    (B, V) instead of (B, S, V).
    """
    sig = signature(cfg)
    period = find_period(sig)
    b, s_tok = tokens.shape

    x = params["embed"].astype(compute_dtype)[tokens]
    if frontend_feats is not None:
        fe = frontend_apply(params["frontend"], frontend_feats.astype(compute_dtype))
        x = jnp.concatenate([fe, x], axis=1)
    s = x.shape[1]
    if cache_index is None:
        positions = jnp.arange(s)
    elif jnp.ndim(cache_index) == 1:
        # slot-indexed serving: per-row entry counts -> per-row positions (B, s)
        positions = cache_index[:, None] + jnp.arange(s)
    else:
        positions = cache_index + jnp.arange(s)

    def body(carry, xs):
        # carry holds the stacked caches (None when uncached); ``r`` is the
        # repeat index every slot's cache is written at
        xact, aux, cc = carry
        slot_params, r = xs

        def inner(xact, aux, slot_params, cc):
            new_caches = []
            for si in range(period):
                xact, nc, st = block_apply(
                    slot_params[si], xact, cfg, sig[si],
                    positions=positions, cache=None if cc is None else cc[si],
                    cache_index=cache_index, layer=r, impl=impl,
                )
                new_caches.append(nc)
                aux = aux + st["aux"]
            return xact, aux, None if cc is None else tuple(new_caches)

        if remat:
            f = jax.checkpoint(inner, policy=jax.checkpoint_policies.nothing_saveable)
        else:
            f = inner
        with jax.named_scope("model.block"):
            xact, aux, cc = f(xact, aux, slot_params, cc)
        return (xact, aux, cc), None

    repeats = cfg.num_layers // period
    carry = (x, jnp.zeros((), jnp.float32), caches)
    # the layer stack: what lies under model.layers and outside model.block
    # is the loop's own work (slicing each layer's weights out of the
    # stacks), read by the benchmark; the caches ride in the carry
    with jax.named_scope("model.layers"):
        if caches is None:
            carry, _ = jax.lax.scan(lambda c, sp: body(c, (sp, None)), carry,
                                    params["slots"])
        else:
            carry, _ = jax.lax.scan(body, carry,
                                    (params["slots"], jnp.arange(repeats)))
    x, aux, new_caches = carry

    if last is not None:
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ).astype(compute_dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    if last is not None:
        logits = logits[:, 0]
    return logits, new_caches, aux


# ---------------------------------------------------------------------------
# losses and steps
# ---------------------------------------------------------------------------


def softmax_xent(logits: Array, labels: Array, mask: Optional[Array] = None):
    """logits: (B,S,V) ; labels: (B,S) int32; mask: (B,S) 1=count."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(params, batch, cfg: ModelConfig, *, impl="auto", remat=True,
            compute_dtype=jnp.bfloat16):
    tokens = batch["tokens"]
    labels = batch["labels"]
    frontend = batch.get("frontend")
    logits, _, aux = forward(
        params, tokens, cfg, frontend_feats=frontend, impl=impl, remat=remat,
        compute_dtype=compute_dtype,
    )
    if frontend is not None:
        # loss only over the text region (frontend positions are prefix)
        f = logits.shape[1] - labels.shape[1]
        logits = logits[:, f:]
    loss = softmax_xent(logits, labels, batch.get("mask"))
    return loss + aux, (loss, aux)


def make_train_step(cfg: ModelConfig, optimizer, *, impl="auto", remat=True,
                    compute_copy_dtype=None):
    """compute_copy_dtype: when set (e.g. jnp.bfloat16), matrix params are
    cast to it ONCE per step before the forward pass, so FSDP all-gathers
    and all weight reads move half the bytes; the f32 master copy and the
    optimizer update stay full precision (classic mixed precision)."""

    def cast_tree(p):
        if compute_copy_dtype is None:
            return p

        def one(a):
            if a.dtype == jnp.float32 and a.ndim >= 2:
                a = a.astype(compute_copy_dtype)
            return a

        return jax.tree.map(one, p)

    def train_step(params, opt_state, batch):
        if compute_copy_dtype is None:
            (total, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, cfg, impl=impl, remat=remat
            )
        else:
            # differentiate wrt the LOW-PRECISION copy: the gradient
            # reduction (the dominant train collective) then moves
            # compute_copy_dtype bytes, and the f32 master update follows.
            params_c = cast_tree(params)
            (total, (loss, aux)), grads_c = jax.value_and_grad(
                lambda p: loss_fn(p, batch, cfg, impl=impl, remat=remat),
                has_aux=True,
            )(params_c)
            grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads_c, params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        from repro.optim.optimizers import apply_updates

        params = apply_updates(params, updates)
        metrics = {"loss": loss, "aux": aux, "total": total}
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, *, impl="auto",
                      compute_dtype=jnp.bfloat16):
    def prefill(params, tokens, caches, frontend_feats=None):
        logits, new_caches, _ = forward(
            params, tokens, cfg, caches=caches, cache_index=jnp.zeros((), jnp.int32),
            frontend_feats=frontend_feats, impl=impl, remat=False,
            compute_dtype=compute_dtype,
        )
        return logits[:, -1], new_caches

    return prefill


def make_decode_step(cfg: ModelConfig, *, impl="auto",
                     compute_dtype=jnp.bfloat16):
    def decode(params, tokens, caches, cache_index):
        """tokens: (B, 1); cache_index: int32 tokens already seen - a scalar
        (lockstep batch) or a (B,) vector (per-slot counts, serving engine)."""
        logits, new_caches, _ = forward(
            params, tokens, cfg, caches=caches, cache_index=cache_index,
            impl=impl, remat=False, compute_dtype=compute_dtype,
        )
        return logits[:, -1], new_caches

    return decode
