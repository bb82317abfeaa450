"""Per-architecture smoke tests (deliverable f): reduced variant, one
forward + one train step + one decode step on CPU, asserting shapes and
finiteness."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.data import synthetic_batch
from repro.models import (
    forward,
    init_caches,
    init_params,
    make_decode_step,
    make_train_step,
)
from repro.models import layers as L
from repro.models.model import block_apply, find_period, signature
from repro.optim import adamw


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = synthetic_batch(cfg, batch=2, seq=32, seed=0)
    logits, _, aux = forward(
        params, batch["tokens"], cfg, frontend_feats=batch.get("frontend"), remat=False
    )
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))

    opt = adamw(1e-3, max_grad_norm=1.0)
    step = jax.jit(make_train_step(cfg, opt))
    ostate = opt.init(params)
    p2, ostate, metrics = step(params, ostate, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    # params changed
    changed = jax.tree.map(lambda a, b: bool((a != b).any()), params, p2)
    assert any(jax.tree.leaves(changed))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_with_cache(arch):
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    caches = init_caches(cfg, batch=2, cache_len=64)
    dec = jax.jit(make_decode_step(cfg))
    toks = jnp.ones((2, 1), jnp.int32)
    logits, caches2 = dec(params, toks, caches, jnp.array(3, jnp.int32))
    assert logits.shape == (2, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    # cache structure preserved
    assert jax.tree.structure(caches) == jax.tree.structure(caches2)


def _decode_vs_forward_err(cfg) -> float:
    """Max |greedy-decode logits - teacher-forced forward logits|."""
    params = init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, cfg.vocab_size)
    full_logits, _, _ = forward(params, toks, cfg, remat=False, compute_dtype=jnp.float32)

    caches = init_caches(cfg, batch=1, cache_len=16, dtype=jnp.float32)
    dec = make_decode_step(cfg, compute_dtype=jnp.float32)
    outs = []
    for t in range(8):
        logits, caches = jax.jit(dec)(
            params, toks[:, t : t + 1], caches, jnp.array(t, jnp.int32)
        )
        outs.append(logits)
    dec_logits = jnp.stack(outs, axis=1)  # (1, 8, V)
    return float(jnp.abs(dec_logits - full_logits).max())


@pytest.mark.parametrize("arch", [
    "qwen2.5-3b",
    "mamba2-370m",
    # xfail RETIRED: dropless MoE dispatch computes every routed token, so
    # a token's output is independent of its dispatch-group size and
    # teacher-forced forward (8-token groups) agrees with single-token
    # decode.
    pytest.param("jamba-v0.1-52b", marks=[pytest.mark.jamba_decode]),
])
def test_decode_matches_forward(arch):
    """Greedy decode logits must match teacher-forced forward logits."""
    err = _decode_vs_forward_err(get_config(arch).reduced())
    assert err < 2e-2, err


def test_sliding_window_decode():
    cfg = get_config("stablelm-1.6b").reduced().with_window(8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    caches = init_caches(cfg, batch=1, cache_len=8)  # ring buffer = window
    dec = jax.jit(make_decode_step(cfg))
    toks = jnp.ones((1, 1), jnp.int32)
    for t in range(20):  # wraps the ring buffer twice
        logits, caches = dec(params, toks, caches, jnp.array(t, jnp.int32))
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


def _block_loop(params, tokens, cfg, caches, cache_index):
    """The cached forward as a plain per-layer loop over ``block_apply``:
    each layer's cache sliced out of its stack, updated alone, restacked."""
    sig = signature(cfg)
    period = find_period(sig)
    x = params["embed"][tokens]
    s = tokens.shape[1]
    positions = (cache_index[:, None] + jnp.arange(s) if jnp.ndim(cache_index)
                 else cache_index + jnp.arange(s))
    new = [[] for _ in range(period)]
    for r in range(cfg.num_layers // period):
        for si in range(period):
            x, nc, _ = block_apply(
                jax.tree.map(lambda a: a[r], params["slots"][si]), x, cfg,
                sig[si], positions=positions,
                cache=jax.tree.map(lambda a: a[r], caches[si]),
                cache_index=cache_index)
            new[si].append(nc)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    return logits, tuple(jax.tree.map(lambda *z: jnp.stack(z), *n)
                         for n in new)


def _mha(cfg):
    return replace(cfg, num_kv_heads=cfg.num_heads)


@pytest.mark.parametrize("cfg,cache_len", [
    pytest.param(replace(get_config("qwen2.5-3b").reduced(), num_layers=3),
                 16, id="gqa_qkv_bias"),
    pytest.param(replace(_mha(get_config("stablelm-1.6b").reduced()),
                         num_layers=3), 16, id="mha"),
    pytest.param(replace(_mha(get_config("stablelm-1.6b").reduced())
                         .with_window(8), num_layers=3), 8,
                 id="sliding_window_ring"),
    pytest.param(replace(get_config("jamba-v0.1-52b").reduced(), num_layers=4,
                         block_pattern="AMAM"), 16, id="ssm_hybrid_period2"),
])
def test_carried_cache_forward_bitwise_matches_block_loop(cfg, cache_len):
    """The layer loop carries the stacked caches and writes each layer's
    new rows in place: prefill, then two vector-index decode steps at
    per-row positions, give bitwise the logits and caches of a per-layer
    loop over ``block_apply`` on each layer's own cache. Caches are bf16;
    compute is f32, since in bf16 XLA may keep excess precision inside a
    fusion and a scan body fuses differently from a Python loop."""
    assert find_period(signature(cfg)) * 2 <= cfg.num_layers
    params = init_params(jax.random.PRNGKey(0), cfg)
    b, p = 3, 6
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, p + 2), 0,
                                cfg.vocab_size)
    run = jax.jit(lambda t, c, i: forward(params, t, cfg, caches=c,
                                          cache_index=i,
                                          compute_dtype=jnp.float32)[:2])
    ref = jax.jit(lambda t, c, i: _block_loop(params, t, cfg, c, i))
    got = want = init_caches(cfg, batch=b, cache_len=cache_len)
    # rows at different positions: the ring config wraps on the last step
    steps = [(tokens[:, :p], jnp.int32(0)),
             (tokens[:, p:p + 1], jnp.asarray([p, p - 1, p - 2], jnp.int32)),
             (tokens[:, p + 1:], jnp.asarray([p + 2, p, p - 1], jnp.int32))]
    for tok, idx in steps:
        lg, got = run(tok, got, idx)
        lg_ref, want = ref(tok, want, idx)
        assert jnp.array_equal(lg, lg_ref)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, bb in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert jnp.array_equal(a, bb)
