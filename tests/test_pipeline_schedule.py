"""Split-executor schedule tests: the 1F1B executor must be loss- and
gradient-compatible with the fill-drain reference (rtol <= 2e-5 at f32),
including uneven masked splits, the Pallas stage-kernel knob, and the
stage-grad re-layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.pipeline import (
    PipelineConfig,
    make_stage_mesh,
    pipeline_step_fn,
    restack_for_stages,
    stage_lengths,
    unstack_stage_grads,
)
from repro.models import init_params

RTOL = 2e-5


def _assert_grads_close(g_ref, g_new, rtol=RTOL):
    assert jax.tree.structure(g_ref) == jax.tree.structure(g_new)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(g_ref)[0],
        jax.tree_util.tree_flatten_with_path(g_new)[0],
    ):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        # rtol on the leaf scale: element-wise rtol is meaningless for the
        # near-zero entries of scatter-sparse grads (embed rows of unseen
        # tokens carry exact zeros on both sides, but neighbours sit at
        # rounding level)
        np.testing.assert_allclose(
            b, a, rtol=rtol, atol=rtol * max(np.abs(a).max(), 1e-8),
            err_msg=jax.tree_util.keystr(path),
        )


def _data(cfg, rows, seq, seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, seq)), jnp.int32)
    return tokens, labels


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "qwen2.5-3b"])
def test_1f1b_matches_fill_drain_single_stage(arch):
    """S=1 exercises the full manual-VJP machinery (stash, loss seeding,
    embed scatter, tied/untied heads) without needing a multi-device mesh."""
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_stage_mesh(1)
    tokens, labels = _data(cfg, rows=4, seq=32)
    fd = pipeline_step_fn(cfg, mesh, (2,), 4,
                          pipe=PipelineConfig(schedule="fill_drain",
                                              compute_dtype="float32"))
    f1 = pipeline_step_fn(cfg, mesh, (2,), 4,
                          pipe=PipelineConfig(schedule="1f1b",
                                              compute_dtype="float32"))
    l0, g0 = jax.jit(fd)(params, tokens, labels)
    l1, g1 = jax.jit(f1)(params, tokens, labels)
    np.testing.assert_allclose(float(l1), float(l0), rtol=RTOL)
    _assert_grads_close(g0, g1)


def test_1f1b_matches_fill_drain_multistage(subproc):
    """Uneven 3-stage split on a real stage mesh: masked active-length
    compute + ppermute hops + per-stage grad re-layout, against jax.grad
    of the fill-drain reference."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.models import init_params
from repro.core.pipeline import PipelineConfig, make_stage_mesh, pipeline_step_fn

cfg = replace(get_config('qwen2.5-3b').reduced(), num_layers=4)
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_stage_mesh(3)
rng = np.random.default_rng(0)
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (6, 16)), jnp.int32)
labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (6, 16)), jnp.int32)
bounds = (1, 3, 4)  # uneven: stage lengths 1/2/1, max_len 2
fd = pipeline_step_fn(cfg, mesh, bounds, 3,
                      pipe=PipelineConfig(schedule="fill_drain", compute_dtype="float32"))
f1 = pipeline_step_fn(cfg, mesh, bounds, 3,
                      pipe=PipelineConfig(schedule="1f1b", compute_dtype="float32"))
l0, g0 = jax.jit(fd)(params, tokens, labels)
l1, g1 = jax.jit(f1)(params, tokens, labels)
assert abs(float(l0) - float(l1)) <= 2e-5 * abs(float(l0)), (float(l0), float(l1))
for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(g0)[0],
                             jax.tree_util.tree_flatten_with_path(g1)[0]):
    a = np.asarray(a, np.float64); b = np.asarray(b, np.float64)
    np.testing.assert_allclose(b, a, rtol=2e-5,
                               atol=2e-5 * max(np.abs(a).max(), 1e-8),
                               err_msg=jax.tree_util.keystr(path))
print('F1B_PARITY_OK', float(l0))
""",
        n_devices=3,
    )
    assert "F1B_PARITY_OK" in out


def test_1f1b_pallas_stage_impl_matches_reference():
    """PipelineConfig.stage_impl='pallas' (fused residual-MLP kernel,
    interpret mode on CPU) is loss/grad-compatible with the reference
    stage implementation through the whole 1F1B executor."""
    cfg = get_config("stablelm-1.6b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_stage_mesh(1)
    tokens, labels = _data(cfg, rows=4, seq=16)
    ref = pipeline_step_fn(cfg, mesh, (2,), 2,
                           pipe=PipelineConfig(compute_dtype="float32"))
    pal = pipeline_step_fn(cfg, mesh, (2,), 2,
                           pipe=PipelineConfig(compute_dtype="float32",
                                               stage_impl="pallas"))
    l0, g0 = jax.jit(ref)(params, tokens, labels)
    l1, g1 = jax.jit(pal)(params, tokens, labels)
    np.testing.assert_allclose(float(l1), float(l0), rtol=RTOL)
    _assert_grads_close(g0, g1)


def test_boundary_validation():
    """Malformed split plans are refused with a clear ValueError before
    they reach shard_map (satellite: stage_lengths/restack_for_stages)."""
    for bad in [(), (2, 2, 4), (3, 2), (0, 2), (-1, 4)]:
        with pytest.raises(ValueError):
            stage_lengths(bad)
    tree = {"w": jnp.zeros((4, 3))}
    with pytest.raises(ValueError):
        restack_for_stages(tree, (1, 3))  # last boundary != num_layers
    with pytest.raises(ValueError):
        restack_for_stages(tree, (2, 2, 4))
    out = restack_for_stages(tree, (1, 4))  # valid: lens 1/3
    assert out["w"].shape == (2, 3, 3)


def test_transport_sync_overlap_bit_identical_multistage(subproc):
    """The double-buffered handoff consumes every buffer on the same tick
    as the synchronous one, so loss AND grads must match bit-for-bit; a
    bf16 wire under f32 compute stays close at bf16 tolerance."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.models import init_params
from repro.core.pipeline import PipelineConfig, make_stage_mesh, pipeline_step_fn

cfg = replace(get_config('qwen2.5-3b').reduced(), num_layers=4)
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_stage_mesh(3)
rng = np.random.default_rng(0)
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (6, 16)), jnp.int32)
labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (6, 16)), jnp.int32)
bounds = (1, 3, 4)
steps = {}
for tr in ('sync', 'overlap'):
    fn = pipeline_step_fn(cfg, mesh, bounds, 3,
                          pipe=PipelineConfig(transport=tr, compute_dtype='float32'))
    steps[tr] = jax.jit(fn)(params, tokens, labels)
l_s, g_s = steps['sync']; l_o, g_o = steps['overlap']
assert float(l_s) == float(l_o), (float(l_s), float(l_o))
for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(g_s)[0],
                             jax.tree_util.tree_flatten_with_path(g_o)[0]):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                  err_msg=jax.tree_util.keystr(path))
# bf16 wire under f32 compute: quantizes each hop, bounded drift
wire = pipeline_step_fn(cfg, mesh, bounds, 3,
                        pipe=PipelineConfig(compute_dtype='float32',
                                            wire_dtype='bfloat16'))
l_w, g_w = jax.jit(wire)(params, tokens, labels)
assert abs(float(l_w) - float(l_s)) <= 3e-2 * abs(float(l_s)), (float(l_w), float(l_s))
print('TRANSPORT_PARITY_OK', float(l_s))
""",
        n_devices=3,
    )
    assert "TRANSPORT_PARITY_OK" in out


def test_1f1b_matches_fill_drain_8stage_uneven(subproc):
    """S=8 uneven split on a real 8-device stage mesh (satellite c):
    overlapped 1F1B vs jax.grad of fill-drain, loss + grads."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.models import init_params
from repro.core.pipeline import PipelineConfig, make_stage_mesh, pipeline_step_fn

cfg = replace(get_config('qwen2.5-3b').reduced(), num_layers=9)
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_stage_mesh(8)
rng = np.random.default_rng(0)
m = 8
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (m, 8)), jnp.int32)
labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (m, 8)), jnp.int32)
bounds = (2, 3, 4, 5, 6, 7, 8, 9)  # stage lens 2/1/1/1/1/1/1/1
fd = pipeline_step_fn(cfg, mesh, bounds, m,
                      pipe=PipelineConfig(schedule="fill_drain", compute_dtype="float32"))
f1 = pipeline_step_fn(cfg, mesh, bounds, m,
                      pipe=PipelineConfig(schedule="1f1b", transport="overlap",
                                          compute_dtype="float32"))
l0, g0 = jax.jit(fd)(params, tokens, labels)
l1, g1 = jax.jit(f1)(params, tokens, labels)
assert abs(float(l0) - float(l1)) <= 2e-5 * abs(float(l0)), (float(l0), float(l1))
for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(g0)[0],
                             jax.tree_util.tree_flatten_with_path(g1)[0]):
    a = np.asarray(a, np.float64); b = np.asarray(b, np.float64)
    np.testing.assert_allclose(b, a, rtol=2e-5,
                               atol=2e-5 * max(np.abs(a).max(), 1e-8),
                               err_msg=jax.tree_util.keystr(path))
print('F1B_8STAGE_OK', float(l0))
""",
        n_devices=8,
        timeout=600,
    )
    assert "F1B_8STAGE_OK" in out


@pytest.mark.parametrize("arch", ["jamba-v0.1-52b", "qwen3-moe-30b-a3b"])
def test_1f1b_mixed_blocks_matches_forward_single_stage(arch):
    """Mixed block types per stage (PR 9): the union-param + lax.switch
    executor on a hybrid SSM/MoE (period-2) and pure-MoE stack must
    match jax.value_and_grad of the plain forward pass, router aux loss
    included, microbatch by microbatch - exact-zero union rows for foreign
    fields must contribute exact-zero grads. The step's routed-row
    counter holds every (token, choice) of each MoE layer."""
    from repro.models import model as M

    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_stage_mesh(1)
    tokens, labels = _data(cfg, rows=2, seq=16)

    def ref_loss(p):
        # mean over the 2 one-row microbatches of cross-entropy + aux
        def one(tok, lab):
            logits, _, aux = M.forward(p, tok[None], cfg,
                                       compute_dtype=jnp.float32, remat=False)
            return M.softmax_xent(logits, lab[None]) + aux
        return (one(tokens[0], labels[0]) + one(tokens[1], labels[1])) / 2

    l0, g0 = jax.jit(jax.value_and_grad(ref_loss))(params)
    f1 = pipeline_step_fn(cfg, mesh, (cfg.num_layers,), 2,
                          pipe=PipelineConfig(compute_dtype="float32"))
    l1, g1, rows = jax.jit(f1)(params, tokens, labels)
    np.testing.assert_allclose(float(l1), float(l0), rtol=RTOL)
    _assert_grads_close(g0, g1)
    moe_layers = [is_moe for _, is_moe, _ in M.signature(cfg)]
    np.testing.assert_array_equal(
        np.asarray(rows).sum(1),
        [tokens.size * cfg.moe.top_k * m for m in moe_layers])


def test_1f1b_mixed_blocks_multistage(subproc):
    """Hybrid period-2 stack split unevenly across a real 2-stage mesh:
    the static per-slot block-kind schedule rides the shard_map scan
    (codes restacked like the union params) and must reproduce the plain
    forward loss/grads, router aux loss included, per microbatch."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.models import model as M
from repro.models.model import init_params
from repro.core.pipeline import PipelineConfig, make_stage_mesh, pipeline_step_fn

base = get_config('jamba-v0.1-52b').reduced()
cfg = replace(base, num_layers=4, block_pattern='AMAM')
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_stage_mesh(2)
rng = np.random.default_rng(0)
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)

def ref_loss(p):
    # mean over the 2 two-row microbatches of cross-entropy + aux
    total = 0.0
    for m in range(2):
        logits, _, aux = M.forward(p, tokens[2 * m:2 * m + 2], cfg,
                                   compute_dtype=jnp.float32, remat=False)
        total = total + M.softmax_xent(logits, labels[2 * m:2 * m + 2]) + aux
    return total / 2

l0, g0 = jax.jit(jax.value_and_grad(ref_loss))(params)
f1 = pipeline_step_fn(cfg, mesh, (1, 4), 2,  # uneven: stage lens 1/3
                      pipe=PipelineConfig(compute_dtype='float32'))
l1, g1, rows = jax.jit(f1)(params, tokens, labels)
assert np.asarray(rows).sum(1).tolist() == [
    tokens.size * cfg.moe.top_k * m for _, m, _ in M.signature(cfg)], rows
assert abs(float(l0) - float(l1)) <= 2e-5 * abs(float(l0)), (float(l0), float(l1))
for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(g0)[0],
                             jax.tree_util.tree_flatten_with_path(g1)[0]):
    a = np.asarray(a, np.float64); b = np.asarray(b, np.float64)
    np.testing.assert_allclose(b, a, rtol=2e-5,
                               atol=2e-5 * max(np.abs(a).max(), 1e-8),
                               err_msg=jax.tree_util.keystr(path))
print('MIXED_MULTISTAGE_OK', float(l0))
""",
        n_devices=2,
    )
    assert "MIXED_MULTISTAGE_OK" in out


def test_fill_drain_rejects_mixed_period():
    """The fill-drain reference stays period-1 only; mixed stacks must
    raise the redirect to the 1F1B schedule, not silently mis-stack."""
    from repro.core.pipeline import pipeline_loss_fn

    cfg = get_config("jamba-v0.1-52b").reduced()
    mesh = make_stage_mesh(1)
    with pytest.raises(AssertionError, match="1f1b"):
        pipeline_loss_fn(cfg, mesh, (cfg.num_layers,), 2)


def test_restack_unstack_roundtrip():
    """unstack_stage_grads inverts restack_for_stages for any split."""
    leaf = jnp.arange(5 * 3 * 2, dtype=jnp.float32).reshape(5, 3, 2)
    tree = {"w": leaf, "b": jnp.arange(5.0)}
    for bounds in [(5,), (2, 5), (1, 2, 5), (3, 4, 5)]:
        stacked = restack_for_stages(tree, bounds)
        s, max_len = len(bounds), max(stage_lengths(bounds))
        assert stacked["w"].shape == (s, max_len, 3, 2)
        back = unstack_stage_grads(stacked, bounds)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(tree[k]))
