"""Compile the main-path device programs for a described TPU v5e chip.

Nothing runs: each test lowers a program with abstract shapes placed on a
described (not attached) v5e and asks the TPU compiler for an executable.
That catches what the CPU and the Pallas interpreter cannot - tiling
rules, scoped-VMEM limits, programs that overflow the chip's HBM - at no
chip time. Shapes come from ``jax.eval_shape``; no array is allocated.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file. The persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back).
"""
import os
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log under the system temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means: no TPU stack
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled):
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need < HBM_BYTES, f"program needs {need / 2**30:.2f} GiB"


def test_ca_attention_compiles_at_sac_defaults(one_chip):
    """The CA actor kernel at ``SACConfig()`` sizes compiles to a Mosaic
    custom call (``interpret=False``: the backend here is the CPU)."""
    from repro.core.agents.action_space import flat_dim
    from repro.core.agents.attention import init_cross_attention
    from repro.core.agents.sac import SACConfig
    from repro.core.env import MHSLEnv
    from repro.core.profiles import resnet101_profile
    from repro.kernels.ca_attention import ca_attention

    cfg = SACConfig()
    env = MHSLEnv(profile=resnet101_profile(batch=1))
    pair_dim = env.obs_dim + flat_dim(env.action_dims)
    params = jax.eval_shape(lambda: init_cross_attention(
        jax.random.PRNGKey(0), env.obs_dim, pair_dim, cfg.attn_dim))
    f32 = jnp.float32
    args = (_placed(params, one_chip),
            jax.ShapeDtypeStruct((cfg.batch, env.obs_dim), f32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((cfg.batch, cfg.hist_len, pair_dim), f32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((cfg.batch, cfg.hist_len), f32,
                                 sharding=one_chip))
    compiled = jax.jit(
        lambda p, o, h, m: ca_attention(p, o, h, m, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_1f1b_step_compiles_at_qwen_widths(topo):
    """The 1F1B train step on a 1-stage mesh of one described chip, at
    qwen2.5-3b widths (152k vocabulary, tied head) cut to 2 layers."""
    from repro.configs import get_config
    from repro.core.pipeline import PipelineConfig, pipeline_step_fn
    from repro.models.model import init_params

    cfg = replace(get_config("qwen2.5-3b"), num_layers=2)
    mesh = Mesh(np.array(topo.devices[:1]), ("stage",))
    rep = NamedSharding(mesh, P())
    step = pipeline_step_fn(cfg, mesh, (2,), 2, pipe=PipelineConfig())
    params = _placed(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)), rep)
    tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32, sharding=rep)
    loss, grads = jax.eval_shape(step, params, tokens, tokens)
    assert loss.shape == () and jax.tree.structure(grads) == \
        jax.tree.structure(params)
    compiled = jax.jit(step).lower(params, tokens, tokens).compile()
    _fits(compiled)


def test_serving_engine_step_compiles_at_qwen_widths(one_chip):
    """The continuous-batching engine step (admission, prefill, decode
    chunk) on one described chip, qwen2.5-3b widths in bf16, 2 layers."""
    from repro.serving import ServeConfig
    from repro.serving.engine import init_engine_state, make_engine_step
    from repro.serving.runners import SingleDeviceRunner

    sc = ServeConfig(reduced=False, num_layers=2, compute_dtype="bfloat16",
                     num_slots=8, prompt_pad=128, max_new=32)
    runner = SingleDeviceRunner(sc.model_config(), compute_dtype=jnp.bfloat16)
    step = make_engine_step(
        runner, num_slots=sc.num_slots, arrival_slots=sc.arrival_slots,
        prompt_pad=sc.prompt_pad, max_new=sc.max_new,
        decode_chunk=sc.decode_chunk)
    params = _placed(jax.eval_shape(sc.init_params), one_chip)
    state = _placed(jax.eval_shape(lambda: init_engine_state(
        runner, sc.num_slots, sc.prompt_pad, sc.max_new)), one_chip)
    a = sc.arrival_slots
    vec = jax.ShapeDtypeStruct((a,), jnp.int32, sharding=one_chip)
    args = (params, state,
            jax.ShapeDtypeStruct((a, sc.prompt_pad), jnp.int32,
                                 sharding=one_chip),
            vec, vec, vec,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()
    assert len(step.trace_count) == 1
    _fits(compiled)


def test_moe_expert_share_compiles_at_qwen3_widths(one_chip):
    """One Qwen3-MoE expert layer's forward and backward on one described
    chip at published widths (8,192 tokens, 128 experts routed top-8, 16
    held, expert width 768), through the chip's path (``impl="ragged"``:
    the backend here is the CPU): grouped-matmul kernels, and no per-block
    copy of the expert weights."""
    import re

    from repro.configs import get_config
    from repro.models import layers as L

    base = get_config("qwen3-moe-30b-a3b")
    cfg = replace(base, moe=replace(base.moe, num_held=16))
    params = _placed(jax.eval_shape(
        lambda: L.init_moe(jax.random.PRNGKey(0), cfg)), one_chip)
    x = jax.ShapeDtypeStruct((1, 8192, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, xx):
        y, aux, _ = L.moe_dropless(p, xx, cfg, impl="ragged")
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    assert not re.search(r"\[\d+,2048,768\][^\n]* gather\(", text)
    _fits(compiled)
