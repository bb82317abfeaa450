"""Attention/MLP/MoE layer unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig, MoEConfig
from repro.models import layers as L


def _qkv(key, b, s, h, kh, hd, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kh, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kh, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("kh", [4, 2, 1])
def test_chunked_matches_dense(window, kh):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 96, 4, kh, 32)
    ref = L.dense_attention(q, k, v, q_offset=0, window=window)
    out = L.chunked_attention(q, k, v, window=window, q_chunk=32, kv_chunk=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_chunked_ragged_length():
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 67, 2, 2, 16)
    ref = L.dense_attention(q, k, v, q_offset=0)
    out = L.chunked_attention(q, k, v, q_chunk=32, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_rope_relative_property():
    """RoPE: attention score depends only on relative distance."""
    hd = 32
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    q = jax.random.normal(k1, (1, 1, 1, hd))
    k = jax.random.normal(k2, (1, 1, 1, hd))

    def score(qpos, kpos):
        cq, sq = L.rope_angles(jnp.array([qpos]), hd, 1e4)
        ck, sk = L.rope_angles(jnp.array([kpos]), hd, 1e4)
        qr = L.apply_rope(q, cq, sq)
        kr = L.apply_rope(k, ck, sk)
        return float(jnp.sum(qr * kr))

    assert abs(score(5, 3) - score(105, 103)) < 1e-4
    assert abs(score(7, 0) - score(17, 10)) < 1e-4


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8)) * 10
    w = jnp.ones((8,))
    y = L.rms_norm(x, w)
    rms = jnp.sqrt(jnp.mean(y * y, axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, atol=1e-3)


def _moe_cfg(e=4, k=2, d=16, f=32):
    return ModelConfig(
        name="t", arch_type="moe", num_layers=2, d_model=d, num_heads=2,
        num_kv_heads=2, d_ff=0, vocab_size=64,
        moe=MoEConfig(num_experts=e, top_k=k, expert_d_ff=f),
    )


def test_moe_matches_dense_computation():
    """Dropless dispatch == the dense per-expert reference == an explicit
    per-expert loop written out here."""
    cfg = _moe_cfg()
    params = L.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux, rows = L.moe_dropless(params, x, cfg)
    y_dense, aux_dense = L.moe_apply_dense(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense), atol=1e-6)
    np.testing.assert_allclose(float(aux), float(aux_dense), rtol=1e-6)
    assert int(rows.sum()) == 2 * 8 * cfg.moe.top_k

    # naive reference
    logits = jnp.einsum("bsd,de->bse", x, params["router"])
    probs = jax.nn.softmax(logits, -1)
    gv, ei = jax.lax.top_k(probs, cfg.moe.top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    y_ref = jnp.zeros_like(x)
    for e in range(cfg.moe.num_experts):
        g = jax.nn.silu(x @ params["w_gate"][e]) * (x @ params["w_up"][e])
        fe = g @ params["w_down"][e]
        w = jnp.where(ei == e, gv, 0.0).sum(-1)
        y_ref = y_ref + fe * w[..., None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    assert float(aux) >= 0


def test_moe_skewed_router_drops_no_token():
    """A router that sends every token to the same top-k experts: each
    token is still computed in full, as in the dense reference."""
    cfg = _moe_cfg()
    params = L.init_moe(jax.random.PRNGKey(0), cfg)
    e = cfg.moe.num_experts
    # only feature 0 reaches the router, and it is the same for every token
    params["router"] = jnp.zeros_like(params["router"]).at[0].set(
        jnp.arange(e, 0, -1, dtype=jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    x = x.at[..., 0].set(1.0)
    y, _, rows = L.moe_dropless(params, x, cfg)
    # experts 0 and 1 take every token; 2 and 3 take none
    assert rows.tolist() == [64, 64, 0, 0]
    y_dense, _ = L.moe_apply_dense(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense), atol=1e-6)
    assert bool(jnp.all(jnp.abs(y).sum(-1) > 0))


def test_moe_config_has_one_dispatch():
    """Dropless is the only MoE dispatch: a property, not an option."""
    from dataclasses import replace

    moe = get_config("qwen3-moe-30b-a3b").moe
    assert moe.dispatch == "dropless"
    with pytest.raises(TypeError):
        replace(moe, dispatch="capacity")


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per_row"])
def test_cached_decode_exact(per_row):
    """One-token cached decode through ``attention_apply`` equals reference
    attention over the cache's valid prefix with the new token's K/V
    written in, for a scalar ``cache_index`` and for a per-row (B,) one
    (the serving engine's slots, each at its own position)."""
    B, C, H, KH, hd = 4, 32, 4, 2, 16
    idxs = (0, 7, 19, 31)
    from repro.kernels.ref import flash_attention_ref

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (B, 1, H * hd))
    ck = jax.random.normal(ks[1], (B, C, KH, hd))
    cv = jax.random.normal(ks[2], (B, C, KH, hd))
    for window in (None, 8):
        cfg = ModelConfig(name="t", arch_type="dense", num_layers=1,
                          d_model=H * hd, num_heads=H, num_kv_heads=KH,
                          head_dim=hd, d_ff=0, vocab_size=8,
                          rope_theta=1e4, attention_window=window)
        p = L.init_attention(ks[3], cfg)
        step = jax.jit(lambda p, x, c, i, pos, cfg=cfg: L.attention_apply(
            p, x, cfg, positions=pos, kv_cache=c, cache_index=i))
        for shift in range(len(idxs)):
            if per_row:
                rows = [idxs[(b + shift) % len(idxs)] for b in range(B)]
                ci = jnp.asarray(rows, jnp.int32)
                pos = ci[:, None] + jnp.arange(1)
            else:
                rows = [idxs[shift]] * B
                ci = jnp.asarray(rows[0], jnp.int32)
                pos = ci + jnp.arange(1)
            out, new = step(p, x, {"k": ck, "v": cv}, ci, pos)

            cos, sin = L.rope_angles(pos, hd, cfg.rope_theta)
            q = L.apply_rope((x @ p["wq"]).reshape(B, 1, H, hd), cos, sin)
            kn = L.apply_rope((x @ p["wk"]).reshape(B, 1, KH, hd), cos, sin)
            vn = (x @ p["wv"]).reshape(B, 1, KH, hd)
            ref = []
            for b, i in enumerate(rows):
                kb, vb = ck[b].at[i].set(kn[b, 0]), cv[b].at[i].set(vn[b, 0])
                np.testing.assert_allclose(np.asarray(new["k"][b]), kb,
                                           atol=1e-6)
                np.testing.assert_allclose(np.asarray(new["v"][b]), vb,
                                           atol=1e-6)
                ref.append(flash_attention_ref(
                    q[b:b + 1], kb[None, :i + 1], vb[None, :i + 1],
                    causal=True, window=window, q_offset=i))
            ref = jnp.concatenate(ref).reshape(B, 1, H * hd) @ p["wo"]
            err = float(jnp.abs(out - ref).max())
            assert err < 1e-5, (window, rows, err)


def test_qkv_bias_used():
    cfg = get_config("qwen2.5-3b").reduced()
    p = L.init_attention(jax.random.PRNGKey(0), cfg)
    assert "bq" in p and "bk" in p and "bv" in p
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model))
    out0, _ = L.attention_apply(p, x, cfg, positions=jnp.arange(8))
    p2 = dict(p)
    p2["bq"] = p["bq"] + 1.0
    out1, _ = L.attention_apply(p2, x, cfg, positions=jnp.arange(8))
    assert float(jnp.abs(out0 - out1).max()) > 1e-6


def test_activations():
    x = jnp.array([-2.0, 0.0, 3.0])
    np.testing.assert_allclose(np.asarray(L.activation_fn("relu2")(x)), [0.0, 0.0, 9.0])
