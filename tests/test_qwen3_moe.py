"""Qwen3-MoE mechanisms at a CPU size: per-head QK-norm in attention (and
through the KV cache), and an expert layer that holds a share of the
experts it routes over."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models.model import (forward, init_caches, init_params,
                                make_decode_step, make_prefill_step)

QWEN3 = "qwen3-moe-30b-a3b"


def test_published_config_states_qwen3():
    cfg = get_config(QWEN3)
    assert cfg.qk_norm and not cfg.qkv_bias and not cfg.tie_embeddings
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.expert_d_ff) == \
        (128, 8, 768)
    assert cfg.moe.router_aux_weight == 0.001 and cfg.moe.held == 128
    assert cfg.source.startswith("https://huggingface.co/Qwen/Qwen3-30B-A3B")
    assert cfg.num_moe_layers == cfg.num_layers == 48


def _rms(x, g, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g


def test_qk_norm_matches_hand_written_per_head_rmsnorm():
    """attention_apply with QK-norm against numpy: q and k normalised per
    head over head_dim (each with its own weight), then RoPE, then causal
    GQA attention."""
    cfg = replace(get_config(QWEN3).reduced(), num_layers=1)
    h, kh, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    p = L.init_attention(ks[0], cfg)
    p["q_norm"] = 1 + 0.3 * jax.random.normal(ks[1], (hd,))
    p["k_norm"] = 1 + 0.3 * jax.random.normal(ks[2], (hd,))
    s = 12
    x = jax.random.normal(ks[3], (2, s, d))
    with jax.default_matmul_precision("highest"):
        got, _ = L.attention_apply(p, x, cfg, positions=jnp.arange(s))
    pn = {k: np.asarray(v, np.float64) for k, v in p.items()}
    xn = np.asarray(x, np.float64)
    q = _rms((xn @ pn["wq"]).reshape(2, s, h, hd), pn["q_norm"], cfg.norm_eps)
    k = _rms((xn @ pn["wk"]).reshape(2, s, kh, hd), pn["k_norm"], cfg.norm_eps)
    v = (xn @ pn["wv"]).reshape(2, s, kh, hd)
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, hd, 2) / hd)
    ang = np.arange(s)[:, None] * inv
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]

    def rope(z):
        z1, z2 = z[..., :hd // 2], z[..., hd // 2:]
        return np.concatenate([z1 * cos - z2 * sin, z1 * sin + z2 * cos], -1)

    q, k = rope(q), rope(k)
    k, v = np.repeat(k, h // kh, axis=2), np.repeat(v, h // kh, axis=2)
    sc = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", w, v).reshape(2, s, h * hd) @ pn["wo"]
    # float32 with highest-precision products against float64: the gap
    # is round-off on outputs of scale ~1
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    # the norm is there: without it the output moves by far more
    p_off = {k: v for k, v in p.items() if k not in ("q_norm", "k_norm")}
    off, _ = L.attention_apply(p_off, x, replace(cfg, qk_norm=False),
                               positions=jnp.arange(s))
    assert float(jnp.max(jnp.abs(off - got))) > 1e-2


def test_qk_norm_cached_decode_matches_forward():
    """Prefill and then token-by-token decode through the KV cache give the
    logits of the uncached forward, with QK-norm and the MoE layer on
    (float32 compute and cache: the gap is round-off)."""
    cfg = get_config(QWEN3).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    for n in ("q_norm", "k_norm"):  # weights away from 1
        w = params["slots"][0]["attn"][n]
        params["slots"][0]["attn"][n] = w + 0.3 * jax.random.normal(
            jax.random.PRNGKey(5), w.shape)
    b, p, steps = 2, 5, 4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, p + steps), 0,
                                cfg.vocab_size)
    full, _, _ = forward(params, tokens, cfg, compute_dtype=jnp.float32)
    caches = init_caches(cfg, b, p + steps, dtype=jnp.float32)
    pre = jax.jit(make_prefill_step(cfg, compute_dtype=jnp.float32))
    dec = jax.jit(make_decode_step(cfg, compute_dtype=jnp.float32))
    lg, caches = pre(params, tokens[:, :p], caches)
    got = [lg]
    for t in range(p, p + steps - 1):
        lg, caches = dec(params, tokens[:, t:t + 1], caches,
                         jnp.asarray(t, jnp.int32))
        got.append(lg)
    got = jnp.stack(got, 1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(full[:, p - 1:p + steps - 1]),
                               rtol=2e-4, atol=2e-4)


def _moe(experts=8, top_k=4, seed=0, t=24):
    cfg = replace(get_config(QWEN3).reduced(), num_layers=1)
    cfg = replace(cfg, moe=replace(cfg.moe, num_experts=experts, top_k=top_k))
    params = L.init_moe(jax.random.PRNGKey(seed), cfg)
    # a router with spread logits, so that the top-k is well separated
    params["router"] = params["router"] * 8.0
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, t // 2,
                                                         cfg.d_model))
    return cfg, params, x


def _share(cfg, params, start, held):
    moe = replace(cfg.moe, num_held=held, expert_start=start)
    return replace(cfg, moe=moe), {
        k: (v if k == "router" else v[start:start + held])
        for k, v in params.items()}


@pytest.mark.parametrize("impl", ["reference", "ragged"])
@pytest.mark.parametrize("held", [2, 4])
def test_expert_shares_sum_to_the_whole_layer(impl, held):
    """Each of E/held shares routes over all E experts and computes only
    its own; their partial outputs sum to the uncut layer's dense
    per-expert reference, every share gives the same aux loss, and the
    shares' routed rows add up to every (token, choice)."""
    cfg, params, x = _moe()
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    want, aux = L.moe_apply_dense(params, x, cfg)
    total, rows = jnp.zeros_like(want), []
    for start in range(0, e, held):
        scfg, sp = _share(cfg, params, start, held)
        y, a, r = L.moe_dropless(sp, x, scfg, impl=impl, block_size=8)
        y_dense, a_dense = L.moe_apply_dense(sp, x, scfg)
        # the dropless share and the dense share agree to round-off
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                                   rtol=1e-5, atol=1e-5)
        assert float(a) == float(aux) == float(a_dense)
        total, rows = total + y, rows + list(np.asarray(r))
    # float32 partial sums of k terms regrouped: round-off only
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert sum(rows) == x.shape[0] * x.shape[1] * k and len(rows) == e


def test_share_gradients_sum_to_the_whole_layer():
    """The router's gradient of the uncut layer is the sum of the shares'
    (each share's expert weights get the uncut layer's gradient of those
    experts), through the ragged path's permutation gathers."""
    cfg, params, x = _moe()
    held = 4

    def loss(p, c, impl):
        y, aux = (L.moe_apply_dense(p, x, c) if impl == "dense" else
                  L.moe_dropless(p, x, c, impl=impl)[:2])
        return jnp.sum(jnp.sin(y)) + aux

    g = jax.grad(loss)(params, cfg, "dense")
    router, experts = jnp.zeros_like(g["router"]), {}
    for start in range(0, cfg.moe.num_experts, held):
        scfg, sp = _share(cfg, params, start, held)
        # sin is not linear: each share's gradient is taken at the whole
        # layer's output, so the loss sees the other shares as constants
        rest = L.moe_apply_dense(params, x, cfg)[0] - L.moe_apply_dense(
            sp, x, scfg)[0]

        def part(p):
            y, aux, _ = L.moe_dropless(p, x, scfg, impl="ragged")
            return jnp.sum(jnp.sin(y + rest)) + aux

        gs = jax.grad(part)(sp)
        router = router + gs["router"]
        for n in ("w_gate", "w_up", "w_down"):
            experts.setdefault(n, []).append(gs[n])
    # the aux term's router gradient is counted once per share
    shares = cfg.moe.num_experts // held
    g_aux = jax.grad(lambda p: L._moe_route(p, x.reshape(-1, cfg.d_model),
                                            cfg)[2])(params)["router"]
    np.testing.assert_allclose(np.asarray(router - (shares - 1) * g_aux),
                               np.asarray(g["router"]), rtol=1e-4, atol=1e-5)
    for n, parts in experts.items():
        np.testing.assert_allclose(np.asarray(jnp.concatenate(parts)),
                                   np.asarray(g[n]), rtol=1e-4, atol=1e-5)


def test_ragged_path_matches_dense_with_gradients():
    """The chip's path (``lax.ragged_dot`` over the sorted rows, permutation
    gathers with gather backward passes) against the dense per-expert
    reference, forward and gradients, on the whole layer."""
    cfg, params, x = _moe(seed=3)

    def loss(p, xx, impl):
        y, aux = (L.moe_apply_dense(p, xx, cfg) if impl == "dense" else
                  L.moe_apply_dropless(p, xx, cfg, impl=impl))
        return jnp.sum(y * y) + aux

    (v0, g0) = jax.value_and_grad(loss, argnums=(0, 1))(params, x, "dense")
    (v1, g1) = jax.value_and_grad(loss, argnums=(0, 1))(params, x, "ragged")
    np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        scale = float(jnp.max(jnp.abs(a))) or 1.0
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-4,
                                   atol=1e-5 * scale)


def test_ragged_path_ignores_rows_past_the_groups(monkeypatch):
    """On a TPU the grouped-matmul kernel leaves the rows past the groups
    unwritten, in its output and in its input's gradient. Emulated here by
    filling them with NaN: the layer's output and every gradient stay
    finite and equal to the dense reference's (a share, so that most rows
    lie past the groups)."""
    real = jax.lax.ragged_dot

    def unwritten(x, group_sizes):
        live = jnp.arange(x.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], x, jnp.nan)

    def ragged_dot(lhs, rhs, group_sizes, **kw):
        @jax.custom_vjp
        def f(a, b, gs):
            return unwritten(real(a, b, gs, **kw), gs)

        def bwd(res, g):
            a, b, gs = res
            _, vjp = jax.vjp(lambda a, b: real(a, b, gs, **kw), a, b)
            da, db = vjp(g)
            return unwritten(da, gs), db, None

        f.defvjp(lambda a, b, gs: (f(a, b, gs), (a, b, gs)), bwd)
        return f(lhs, rhs, group_sizes)

    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)
    cfg, params, x = _moe(seed=4)
    scfg, sp = _share(cfg, params, 2, 2)

    def loss(p, xx, impl):
        y, aux = (L.moe_apply_dense(p, xx, scfg) if impl == "dense" else
                  L.moe_apply_dropless(p, xx, scfg, impl=impl))
        return jnp.sum(y * y) + aux

    v0, g0 = jax.value_and_grad(loss, argnums=(0, 1))(sp, x, "dense")
    v1, g1 = jax.value_and_grad(loss, argnums=(0, 1))(sp, x, "ragged")
    np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        scale = float(jnp.max(jnp.abs(a))) or 1.0
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-4,
                                   atol=1e-5 * scale)
