"""Dropless MoE dispatch parity (tentpole PR 9).

``layers.moe_apply_dropless`` (stable-sort grouping + block-padded
grouped matmul) must be BITWISE-equal to the dense per-expert reference
``layers.moe_apply_dense`` - same routing (shared ``_moe_route``), same
per-row arithmetic, merely regrouped. The dropless path computes every
routed pair, so outputs are independent of grouping and decode agrees
with prefill (what retired the ``jamba_decode`` xfail) - pinned here
directly by the block-size and decode-slice invariance tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models.model import init_block, signature

ARCHS = ["qwen3-moe-30b-a3b", "jamba-v0.1-52b"]


def _setup(arch, seed=0, b=2, s=16):
    cfg = get_config(arch).reduced()
    slot = next(sig for sig in signature(cfg) if sig[1])  # a MoE slot
    params = init_block(jax.random.PRNGKey(seed), cfg, slot)["moe"]
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, s, cfg.d_model)), jnp.float32)
    return cfg, params, x


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("block_size", [8, 32])
def test_dropless_reference_bitwise_vs_dense(arch, block_size):
    cfg, params, x = _setup(arch)
    y_dense, aux_dense = L.moe_apply_dense(params, x, cfg)
    y_ref, aux_ref = L.moe_apply_dropless(
        params, x, cfg, impl="reference", block_size=block_size)
    assert jnp.array_equal(y_dense, y_ref), (
        f"dropless(block_size={block_size}) != dense per-expert reference")
    np.testing.assert_allclose(np.asarray(aux_ref), np.asarray(aux_dense),
                               rtol=1e-6)


@pytest.mark.parametrize("arch", ARCHS)
def test_dropless_pallas_matches_dense(arch):
    cfg, params, x = _setup(arch)
    y_dense, _ = L.moe_apply_dense(params, x, cfg)
    y_pal, _ = L.moe_apply_dropless(params, x, cfg, impl="pallas",
                                    block_size=32)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_dense),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("arch", ARCHS)
def test_dropless_decode_slice_bitwise(arch):
    """Group-size independence: dispatching a single decode position
    alone must produce BITWISE the same rows as dispatching it inside the
    full prefill batch."""
    cfg, params, x = _setup(arch)
    y_full, _ = L.moe_apply_dropless(params, x, cfg, impl="reference",
                                     block_size=32)
    y_last, _ = L.moe_apply_dropless(params, x[:, -1:], cfg,
                                     impl="reference", block_size=32)
    assert jnp.array_equal(y_full[:, -1:], y_last)


def test_dropless_grads_match_dense():
    cfg, params, x = _setup("qwen3-moe-30b-a3b")

    def loss(impl):
        def f(p, xx):
            if impl == "dense":
                y, _ = L.moe_apply_dense(p, xx, cfg)
            else:
                y, _ = L.moe_apply_dropless(p, xx, cfg, impl="reference",
                                            block_size=32)
            return jnp.mean(y * y)
        return jax.value_and_grad(f, argnums=(0, 1))

    v_dense, g_dense = jax.jit(loss("dense"))(params, x)
    v_drop, g_drop = jax.jit(loss("dropless"))(params, x)
    np.testing.assert_allclose(float(v_drop), float(v_dense), rtol=2e-6)
    flat_dense = jax.tree.leaves(g_dense)
    flat_drop = jax.tree.leaves(g_drop)
    assert len(flat_dense) == len(flat_drop)
    for a, b in zip(flat_dense, flat_drop):
        assert bool(jnp.all(jnp.isfinite(b)))
        scale = float(jnp.max(jnp.abs(a))) or 1.0
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-5 * scale)
