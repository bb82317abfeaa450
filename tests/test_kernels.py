"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import ca_attention, flash_attention, ssd_scan
from repro.kernels.ref import flash_attention_ref, ssd_scan_ref

FLASH_SHAPES = [
    # (B, S, H, KH, hd, window, q_blk, kv_blk)
    (1, 128, 2, 2, 32, None, 64, 64),
    (2, 256, 4, 2, 64, None, 128, 128),
    (1, 200, 4, 1, 32, None, 64, 64),  # ragged seq, MQA
    (2, 256, 8, 2, 64, 64, 64, 64),  # sliding window
    (1, 512, 2, 2, 16, 128, 128, 64),  # window, uneven blocks
]


@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(shape, dtype):
    b, s, h, kh, hd, win, qb, kb = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kh, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kh, hd), dtype)
    out = flash_attention(q, k, v, window=win, q_blk=qb, kv_blk=kb, interpret=True)
    ref = flash_attention_ref(q, k, v, window=win)
    atol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol
    )


def test_flash_attention_q_offset():
    """Chunked decode-style usage: query block at an offset into the kv."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    skv, sq, off = 128, 32, 96
    q = jax.random.normal(ks[0], (1, sq, 2, 32))
    k = jax.random.normal(ks[1], (1, skv, 2, 32))
    v = jax.random.normal(ks[2], (1, skv, 2, 32))
    out = flash_attention(q, k, v, q_offset=off, q_blk=32, kv_blk=32, interpret=True)
    ref = flash_attention_ref(q, k, v, q_offset=off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


SSD_SHAPES = [
    # (B, S, H, P, N, chunk)
    (1, 64, 2, 16, 8, 16),
    (2, 128, 4, 32, 16, 32),
    (1, 96, 2, 64, 128, 64),
    (1, 80, 1, 8, 4, 32),  # ragged
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_scan_sweep(shape):
    b, s, h, p, n, chunk = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[4], (b, s, n))
    y, hL = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    yr, hr = ssd_scan_ref(x, dt, a, bm, cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4)
    np.testing.assert_allclose(np.asarray(hL), np.asarray(hr), atol=5e-4)


CA_SHAPES = [
    # (batch, obs_dim, pair_dim, I, attn_dim, blk)
    (1, 10, 14, 4, 8, 128),
    (7, 25, 51, 4, 64, 4),  # ragged batch, tiny blocks
    (128, 25, 51, 4, 64, 128),
    (130, 16, 32, 8, 32, 64),  # ragged vs block size, longer history
]


@pytest.mark.parametrize("shape", CA_SHAPES)
def test_ca_attention_matches_reference(shape):
    """The fused Pallas CA kernel reproduces agents.attention's
    cross_attention (current-state row) on CPU interpret mode, including
    all-masked rows and partial histories."""
    from repro.core.agents.attention import cross_attention, init_cross_attention

    b, obs_dim, pair_dim, i, c, blk = shape
    params = init_cross_attention(jax.random.PRNGKey(0), obs_dim, pair_dim, c)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    obs = jax.random.normal(ks[0], (b, obs_dim))
    hist = jax.random.normal(ks[1], (b, i, pair_dim))
    mask = (jax.random.uniform(ks[2], (b, i)) > 0.4).astype(jnp.float32)
    mask = mask.at[0].set(0.0)  # row with no history -> zero summary

    ref = jax.vmap(lambda o, h, m: cross_attention(params, o, h, m))(
        obs, hist, mask)
    out = ca_attention(params, obs, hist, mask, blk=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out[0, obs_dim:]), 0.0, atol=1e-7)


def test_ca_attention_grads_match_reference():
    """The kernel's custom VJP (slim-reference backward) reproduces the
    full reference's gradients - including wq_h's exact zero."""
    from repro.core.agents.attention import cross_attention, init_cross_attention

    b, obs_dim, pair_dim, i, c = 16, 12, 20, 4, 16
    params = init_cross_attention(jax.random.PRNGKey(0), obs_dim, pair_dim, c)
    obs = jax.random.normal(jax.random.PRNGKey(1), (b, obs_dim))
    hist = jax.random.normal(jax.random.PRNGKey(2), (b, i, pair_dim))
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (b, i)) > 0.3
            ).astype(jnp.float32)
    tgt = jax.random.normal(jax.random.PRNGKey(4), (b, obs_dim + c))

    def loss_kernel(p):
        return jnp.sum((ca_attention(p, obs, hist, mask) - tgt) ** 2)

    def loss_ref(p):
        out = jax.vmap(lambda o, h, m: cross_attention(p, o, h, m))(
            obs, hist, mask)
        return jnp.sum((out - tgt) ** 2)

    gk = jax.grad(loss_kernel)(params)
    gr = jax.grad(loss_ref)(params)
    for name in ("wq_s", "wk", "wv", "wq_h"):
        np.testing.assert_allclose(np.asarray(gk[name]), np.asarray(gr[name]),
                                   atol=2e-4, rtol=2e-4, err_msg=name)
    np.testing.assert_array_equal(np.asarray(gk["wq_h"]), 0.0)


def test_ca_attention_low_precision_mask_safe():
    """The kernel's finfo-based masking survives fp16/bf16 scores (a -1e9
    literal overflows fp16 to -inf and NaNs fully-masked rows)."""
    from repro.core.agents.attention import init_cross_attention

    b, obs_dim, pair_dim, i, c = 9, 12, 20, 4, 16
    params = init_cross_attention(jax.random.PRNGKey(0), obs_dim, pair_dim, c)
    obs = jax.random.normal(jax.random.PRNGKey(1), (b, obs_dim))
    hist = jax.random.normal(jax.random.PRNGKey(2), (b, i, pair_dim))
    mask = jnp.zeros((b, i)).at[1:, :2].set(1.0)
    ref = np.asarray(ca_attention(params, obs, hist, mask))
    for dtype in (jnp.bfloat16, jnp.float16):
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        out = ca_attention(cast, obs.astype(dtype), hist.astype(dtype),
                           mask.astype(dtype))
        out = np.asarray(out, np.float32)
        assert np.isfinite(out).all(), dtype
        np.testing.assert_allclose(out, ref, atol=0.15)


def test_model_attention_pallas_path():
    """attention_apply(impl='pallas') agrees with the default path."""
    from repro.configs import get_config
    from repro.models import layers as L

    cfg = get_config("stablelm-1.6b").reduced()
    params = L.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    pos = jnp.arange(64)
    ref, _ = L.attention_apply(params, x, cfg, positions=pos, impl="dense")
    out, _ = L.attention_apply(params, x, cfg, positions=pos, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2)


def test_model_ssd_pallas_path():
    from repro.configs import get_config
    from repro.models import ssm as S

    cfg = get_config("mamba2-370m").reduced()
    params = S.init_mamba(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model))
    ref, (h0, _) = S.mamba_apply(params, x, cfg, use_pallas=False)
    out, (h1, _) = S.mamba_apply(params, x, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)
    np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), atol=1e-3)


STAGE_SHAPES = [
    # (B, S, D, F, blk)
    (1, 32, 64, 128, 128),
    (2, 48, 64, 160, 32),   # ragged rows vs block size
    (3, 37, 128, 96, 64),   # ragged, F < D
]


@pytest.mark.parametrize("shape", STAGE_SHAPES)
@pytest.mark.parametrize("activation", ["swiglu", "gelu", "relu2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stage_mlp_block_forward(shape, activation, dtype):
    """Fused residual stage kernel vs the models.layers reference."""
    from repro.kernels.stage_block import stage_mlp_block
    from repro.models.layers import init_mlp, mlp_block

    b, s, d, f, blk = shape
    params = init_mlp(jax.random.PRNGKey(0), d, f, activation)
    norm_w = jnp.ones((d,)) + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (d,))
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, d), dtype)
    out = stage_mlp_block(norm_w, params, x, activation=activation, blk=blk,
                          interpret=True)
    ref = mlp_block(norm_w, params, x, activation)
    assert out.dtype == x.dtype and out.shape == x.shape
    # f32: the kernel tiles rows and XLA reassociates the D/F reductions
    # differently, so outputs agree to a few ulps OF THE OUTPUT SCALE, not
    # to a fixed absolute bound - relu2 squares its input, so its outputs
    # (and their rounding) run larger (|out| ~7, worst miss 4.2e-7 of the
    # scale). Element-wise rtol is meaningless here: the residual sum
    # cancels to near zero in places. 1e-6 of the scale is ~8 ulps at the
    # largest output.
    # bf16 tolerance covers the kernel's EXTRA precision: it accumulates
    # matmuls in fp32 where the reference rounds between einsums
    ref32 = np.asarray(ref, np.float32)
    atol = 1e-6 * np.abs(ref32).max() if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), ref32, atol=atol)


@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_stage_mlp_block_grads_match_reference(activation):
    """With a FIXED cotangent, the kernel's custom VJP runs the reference
    VJP (same function, same residuals), so params/norm/input grads agree
    to compilation-level reassociation noise (~1e-7 rel; the two VJPs are
    compiled into different programs, so bitwise equality is not
    guaranteed)."""
    from repro.kernels.stage_block import stage_mlp_block
    from repro.models.layers import init_mlp, mlp_block

    d, f, b, s = 64, 96, 2, 19
    params = init_mlp(jax.random.PRNGKey(0), d, f, activation)
    norm_w = jnp.ones((d,)) * 1.05
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, d))
    g = jax.random.normal(jax.random.PRNGKey(2), (b, s, d))
    _, vjp_k = jax.vjp(
        lambda nw, p, xx: stage_mlp_block(nw, p, xx, activation=activation,
                                          blk=16, interpret=True),
        norm_w, params, x)
    _, vjp_r = jax.vjp(
        lambda nw, p, xx: mlp_block(nw, p, xx, activation), norm_w, params, x)
    for a, b_ in zip(jax.tree.leaves(vjp_k(g)), jax.tree.leaves(vjp_r(g))):
        a, b_ = np.asarray(a, np.float64), np.asarray(b_, np.float64)
        np.testing.assert_allclose(a, b_, rtol=1e-6,
                                   atol=1e-6 * max(np.abs(b_).max(), 1e-8))
