"""Sharding rules + multi-device subprocess tests (pipeline, pjit train)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.distribution.sharding import spec_for_param
from jax.sharding import PartitionSpec as P


class FakeMesh:
    axis_names = ("data", "model")
    class devices:  # noqa: D106
        shape = (16, 16)


def test_spec_for_param_rules():
    cfg = get_config("qwen2.5-3b")
    mesh = FakeMesh()
    assert spec_for_param("embed", (cfg.vocab_size, cfg.d_model), cfg, mesh) == P("model", "data")
    assert spec_for_param("slots/0/attn/wq", (36, 2048, 2048), cfg, mesh) == P(None, "data", "model")
    assert spec_for_param("slots/0/attn/wo", (36, 2048, 2048), cfg, mesh) == P(None, "model", "data")
    assert spec_for_param("slots/0/norm1", (36, 2048), cfg, mesh) == P(None, None)
    # indivisible dims are not sharded
    assert spec_for_param("slots/0/attn/wq", (36, 100, 2048), cfg, mesh) == P(None, None, "model")
    # MoE experts on the model axis
    moe = get_config("qwen3-moe-30b-a3b")
    assert spec_for_param("slots/0/moe/w_up", (48, 128, 2048, 768), moe, mesh) == P(
        None, "model", "data", None
    )


def test_batch_axes_divisibility():
    from repro.distribution.sharding import batch_axes

    class M3:
        axis_names = ("pod", "data", "model")
        class devices:  # noqa: D106
            shape = (2, 16, 16)

    assert batch_axes(M3(), 256) == ("pod", "data")
    assert batch_axes(M3(), 2) == ("pod",)
    assert batch_axes(M3(), 1) is None
    assert batch_axes(FakeMesh(), 128) == ("data",)


def test_pipeline_matches_reference(subproc):
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import init_params, loss_fn
from repro.core.pipeline import pipeline_loss_fn, make_stage_mesh

cfg = get_config('stablelm-1.6b').reduced()
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_stage_mesh(2)
rng = np.random.default_rng(0)
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
pl = pipeline_loss_fn(cfg, mesh, boundaries=(1, 2), n_microbatches=2)
loss_pipe = float(jax.jit(pl)(params, tokens, labels))
ref = float(loss_fn(params, {'tokens': tokens, 'labels': labels}, cfg, remat=False)[0])
assert abs(loss_pipe - ref) < 5e-3, (loss_pipe, ref)
g = jax.grad(lambda p: pl(p, tokens, labels))(params)
assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))
print('PIPELINE_OK', loss_pipe, ref)
""",
        n_devices=2,
    )
    assert "PIPELINE_OK" in out


def test_uneven_pipeline_split(subproc):
    """RL-style uneven split (3 stages of a 4-layer model: 2/1/1)."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.models import init_params, loss_fn
from repro.core.pipeline import pipeline_loss_fn, make_stage_mesh

cfg = replace(get_config('qwen2.5-3b').reduced(), num_layers=4)
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_stage_mesh(3)
rng = np.random.default_rng(0)
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (6, 16)), jnp.int32)
labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (6, 16)), jnp.int32)
pl = pipeline_loss_fn(cfg, mesh, boundaries=(2, 3, 4), n_microbatches=3)
loss_pipe = float(jax.jit(pl)(params, tokens, labels))
ref = float(loss_fn(params, {'tokens': tokens, 'labels': labels}, cfg, remat=False)[0])
assert abs(loss_pipe - ref) < 5e-3, (loss_pipe, ref)
print('UNEVEN_OK')
""",
        n_devices=3,
    )
    assert "UNEVEN_OK" in out


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "qwen3-moe-30b-a3b"])
def test_sharded_train_step_runs(subproc, arch):
    """jit train step on a 2x2 host mesh with real (reduced) params, from
    the parameter, optimizer-state and batch shardings alone: its loss is
    the unsharded step's."""
    out = subproc(
        f"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import init_params, make_train_step
from repro.distribution.sharding import param_shardings
from repro.distribution.mesh import make_host_mesh
from repro.optim import adamw
from repro.data import synthetic_batch

cfg = get_config({arch!r}).reduced()
mesh = make_host_mesh(2, 2)
params = init_params(jax.random.PRNGKey(0), cfg)
opt = adamw(1e-3)
ostate = opt.init(params)
batch = synthetic_batch(cfg, 4, 32)
_, _, m_ref = jax.jit(make_train_step(cfg, opt))(params, ostate, batch)

psh = param_shardings(jax.eval_shape(lambda: params), cfg, mesh)
osh = param_shardings(jax.eval_shape(lambda: ostate), cfg, mesh)
bsh = {{k: NamedSharding(mesh, P('data', *([None]*(v.ndim-1))))
        for k, v in batch.items()}}
put = lambda t, sh: jax.tree.map(jax.device_put, t, sh)
step = jax.jit(make_train_step(cfg, opt), in_shardings=(psh, osh, bsh),
               out_shardings=(psh, osh, None))
p2, o2, m = step(put(params, psh), put(ostate, osh), put(batch, bsh))
loss, ref = float(m['loss']), float(m_ref['loss'])
assert jnp.isfinite(loss), m
assert abs(loss - ref) <= 1e-3 * abs(ref), (loss, ref)
print('SHARDED_TRAIN_OK', loss, ref)
""",
        n_devices=4,
    )
    assert "SHARDED_TRAIN_OK" in out
