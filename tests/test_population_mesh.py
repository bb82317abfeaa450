"""Mesh-sharded population training + checkpoint/resume (pinned parity).

Three contracts from the sharding layer:

* a 1-device population mesh is BIT-IDENTICAL to the plain vmap path
  (``train_sac`` and ``train_population``) - the mesh only places data;
* a multi-device mesh sharding the scenario axis keeps per-scenario math
  on one device, so even the 4-way-sharded population matches the vmap
  path exactly (subprocess with forced host devices);
* stopping at a checkpoint and resuming replays the exact episode-reward
  trajectory of an uninterrupted run (``train_sac`` and
  ``train_population``, including a sharded resume).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.train_state import (
    latest_checkpoint_step,
    load_train_checkpoint,
    save_train_checkpoint,
)
from repro.core.agents.loops import train_sac
from repro.core.agents.sac import SACConfig
from repro.core.env import MHSLEnv
from repro.core.profiles import resnet101_profile
from repro.core.scenario import (
    scenario_grid,
    stack_scenarios,
    train_population,
)
from repro.distribution.mesh import make_population_mesh


@pytest.fixture(scope="module")
def env():
    return MHSLEnv(profile=resnet101_profile(batch=1))


def _trees_equal(a, b) -> bool:
    return all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def test_train_sac_one_device_mesh_bit_identical(env):
    cfg = SACConfig()
    kw = dict(episodes=10, warmup_episodes=4, seed=5, num_envs=2)
    ref = train_sac(env, cfg, **kw)
    mesh = train_sac(env, cfg, mesh=make_population_mesh(1), **kw)
    assert mesh.episode_reward == ref.episode_reward
    assert mesh.episode_leak == ref.episode_leak
    assert mesh.states_explored == ref.states_explored
    assert _trees_equal(mesh.params, ref.params)


def test_train_population_one_device_mesh_bit_identical(env):
    cfg = SACConfig()
    scens = stack_scenarios(
        scenario_grid(env.scenario(), monitor_prob=[0.3, 0.8])
    )
    kw = dict(episodes=8, warmup_episodes=3, seed=5, num_envs=2)
    ref = train_population(env, cfg, scens, **kw)
    mesh = train_population(env, cfg, scens,
                            mesh=make_population_mesh(1), **kw)
    for s in range(2):
        assert mesh.results[s].episode_reward == ref.results[s].episode_reward
        assert mesh.results[s].episode_leak == ref.results[s].episode_leak
    assert _trees_equal(mesh.params, ref.params)


def test_sharded_population_multi_device_parity(subproc):
    """4-way scenario sharding matches the single-device vmap path exactly:
    each scenario's computation stays whole on its shard."""
    out = subproc(
        """
import jax
from repro.core.agents.sac import SACConfig
from repro.core.env import MHSLEnv
from repro.core.profiles import resnet101_profile
from repro.core.scenario import scenario_grid, stack_scenarios, train_population
from repro.distribution.mesh import make_population_mesh

env = MHSLEnv(profile=resnet101_profile(batch=1))
cfg = SACConfig()
scens = stack_scenarios(scenario_grid(env.scenario(),
                                      monitor_prob=[0.3, 0.5, 0.7, 0.9]))
kw = dict(episodes=8, warmup_episodes=3, seed=5, num_envs=2)
ref = train_population(env, cfg, scens, **kw)
mesh = make_population_mesh(4)
shd = train_population(env, cfg, scens, mesh=mesh, **kw)
leaf = jax.tree.leaves(shd.params)[0]
assert "env" in leaf.sharding.mesh.axis_names, leaf.sharding
for s in range(4):
    assert shd.results[s].episode_reward == ref.results[s].episode_reward, s
    assert shd.results[s].episode_leak == ref.results[s].episode_leak, s
print('SHARDED_POPULATION_OK')
""",
        n_devices=4,
    )
    assert "SHARDED_POPULATION_OK" in out


def test_pipeline_on_stage_env_mesh_parity(subproc):
    """2-D (stage x env) mesh: the split executor runs pipelined stage
    compute with the microbatch rows sharded over the env axis, matching
    the 1-D stage mesh at f32 tolerance (pmean-of-means reassociation)."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.models import init_params
from repro.core.pipeline import PipelineConfig, make_stage_mesh, pipeline_step_fn
from repro.distribution.mesh import make_stage_env_mesh
from repro.distribution.sharding import (
    microbatch_sharding, population_axes, stage_sharding,
)

cfg = replace(get_config('qwen2.5-3b').reduced(), num_layers=4)
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
m = 2
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (m * 4, 16)), jnp.int32)
labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (m * 4, 16)), jnp.int32)
bounds = (1, 4)
pipe = PipelineConfig(compute_dtype='float32')
l0, g0 = jax.jit(pipeline_step_fn(cfg, make_stage_mesh(2), bounds, m,
                                  pipe=pipe))(params, tokens, labels)

mesh2 = make_stage_env_mesh(2, 2)
assert mesh2.devices.shape == (2, 2)
assert mesh2.axis_names == ('stage', 'env')
assert population_axes(mesh2, 2) == 'env'  # train_population picks env by name
assert microbatch_sharding(mesh2, 3).spec == P(None, 'env', None)
assert stage_sharding(mesh2, 2).spec == P('stage', None)
l1, g1 = jax.jit(pipeline_step_fn(cfg, mesh2, bounds, m, pipe=pipe,
                                  env_axis='env'))(params, tokens, labels)
assert abs(float(l0) - float(l1)) <= 1e-6 * abs(float(l0)), (float(l0), float(l1))
for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(g0)[0],
                             jax.tree_util.tree_flatten_with_path(g1)[0]):
    a = np.asarray(a, np.float64); b = np.asarray(b, np.float64)
    np.testing.assert_allclose(b, a, rtol=1e-5,
                               atol=1e-5 * max(np.abs(a).max(), 1e-8),
                               err_msg=jax.tree_util.keystr(path))
print('STAGE_ENV_PIPELINE_OK', float(l0))
""",
        n_devices=4,
    )
    assert "STAGE_ENV_PIPELINE_OK" in out


def test_train_population_on_stage_env_mesh(subproc):
    """train_population drives the 2-D stage x env mesh unchanged: the
    scenario axis shards over 'env' (picked by name), stage rows stay
    replicated, and the results match the vmap path exactly."""
    out = subproc(
        """
import jax
from repro.core.agents.sac import SACConfig
from repro.core.env import MHSLEnv
from repro.core.profiles import resnet101_profile
from repro.core.scenario import scenario_grid, stack_scenarios, train_population
from repro.distribution.mesh import make_stage_env_mesh

env = MHSLEnv(profile=resnet101_profile(batch=1))
cfg = SACConfig()
scens = stack_scenarios(scenario_grid(env.scenario(), monitor_prob=[0.3, 0.8]))
kw = dict(episodes=6, warmup_episodes=3, seed=5, num_envs=2)
ref = train_population(env, cfg, scens, **kw)
mesh = make_stage_env_mesh(2, 2)
shd = train_population(env, cfg, scens, mesh=mesh, **kw)
leaf = jax.tree.leaves(shd.params)[0]
assert "env" in leaf.sharding.mesh.axis_names, leaf.sharding
for s in range(2):
    assert shd.results[s].episode_reward == ref.results[s].episode_reward, s
    assert shd.results[s].episode_leak == ref.results[s].episode_leak, s
print('STAGE_ENV_POPULATION_OK')
""",
        n_devices=4,
        timeout=600,
    )
    assert "STAGE_ENV_POPULATION_OK" in out


def test_train_sac_checkpoint_resume_bit_identical(env, tmp_path):
    """Save mid-training, resume, and the episode-reward trajectory is
    bit-identical to an uninterrupted run (the paper's long population
    studies can stop/restart without perturbing the curves)."""
    cfg = SACConfig()
    kw = dict(warmup_episodes=4, seed=5, num_envs=2)
    ref = train_sac(env, cfg, episodes=12, **kw)

    ck = os.fspath(tmp_path / "sac")
    part = train_sac(env, cfg, episodes=6, checkpoint_dir=ck,
                     checkpoint_every=2, **kw)
    assert part.episode_reward == ref.episode_reward[:6]
    assert latest_checkpoint_step(ck) == 6

    res = train_sac(env, cfg, episodes=12, checkpoint_dir=ck,
                    checkpoint_every=4, **kw)
    assert res.episode_reward == ref.episode_reward
    assert res.episode_leak == ref.episode_leak
    assert res.episode_violation == ref.episode_violation
    assert res.states_explored == ref.states_explored
    assert _trees_equal(res.params, ref.params)
    # the finished run saved its final state too
    assert latest_checkpoint_step(ck) == 12


def test_train_population_checkpoint_resume_bit_identical(env, tmp_path):
    cfg = SACConfig()
    scens = stack_scenarios(
        scenario_grid(env.scenario(), know_eave_locations=[1.0, 0.0])
    )
    kw = dict(warmup_episodes=3, seed=5, num_envs=2)
    ref = train_population(env, cfg, scens, episodes=8, **kw)

    ck = os.fspath(tmp_path / "pop")
    train_population(env, cfg, scens, episodes=4, checkpoint_dir=ck,
                     checkpoint_every=2, **kw)
    res = train_population(env, cfg, scens, episodes=8, checkpoint_dir=ck,
                           checkpoint_every=2, **kw)
    for s in range(2):
        assert res.results[s].episode_reward == ref.results[s].episode_reward
        assert res.results[s].states_explored == ref.results[s].states_explored
    assert _trees_equal(res.params, ref.params)


def test_resume_rejects_mismatched_run(env, tmp_path):
    """A checkpoint written under different loop knobs (seed here) must be
    a hard error, not a silent resume of someone else's trajectory."""
    cfg = SACConfig()
    ck = os.fspath(tmp_path / "sac")
    train_sac(env, cfg, episodes=4, warmup_episodes=2, seed=5, num_envs=2,
              checkpoint_dir=ck, checkpoint_every=2)
    with pytest.raises(ValueError, match="cannot resume"):
        train_sac(env, cfg, episodes=8, warmup_episodes=2, seed=6,
                  num_envs=2, checkpoint_dir=ck)
    with pytest.raises(ValueError, match="past the requested"):
        train_sac(env, cfg, episodes=2, warmup_episodes=2, seed=5,
                  num_envs=2, checkpoint_dir=ck)


def test_orphan_checkpoint_ignored(tmp_path):
    """An npz without its json (crash between the two writes) is not
    offered for resume."""
    state = {"a": jnp.zeros((2,))}
    d = os.fspath(tmp_path / "ck")
    save_train_checkpoint(d, 2, state, {"ep": 2})
    # simulate a crash mid-write of step 4: npz lands, json does not
    os.replace(os.path.join(d, "step_00000002.npz"),
               os.path.join(d, "step_00000004.npz"))
    os.remove(os.path.join(d, "LATEST"))
    assert latest_checkpoint_step(d) is None
    save_train_checkpoint(d, 6, state, {"ep": 6})
    assert latest_checkpoint_step(d) == 6


def test_checkpoint_store_roundtrip(tmp_path):
    """Unit-level: save/load with LATEST bookkeeping and sharding-aware
    restore onto a 1-device mesh placement."""
    from repro.distribution import population as PD

    state = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(3, 2),
        "b": {"k": jax.random.PRNGKey(7)},
    }
    host = {"ep": 4, "curve": [1.0, 2.0], "seen": [3, 9]}
    d = os.fspath(tmp_path / "ck")
    assert latest_checkpoint_step(d) is None
    save_train_checkpoint(d, 2, state, host)
    save_train_checkpoint(d, 4, state, host)
    assert latest_checkpoint_step(d) == 4

    mesh = make_population_mesh(1)
    like = PD.shard_population(state, mesh, 3)
    step, dev, h = load_train_checkpoint(d, like)
    assert step == 4 and h["ep"] == 4 and h["seen"] == [3, 9]
    assert _trees_equal(dev, state)
    assert np.asarray(dev["b"]["k"]).dtype == np.uint32
