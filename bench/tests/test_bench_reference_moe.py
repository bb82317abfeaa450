"""The program's Qwen3-MoE expert share (QK-norm attention, an expert layer
holding 4 of the 8 experts it routes over, untied head) through its normal
training path, the 1F1B step, against the plain reference of
``bench/reference_moe.py``, at a CPU size with seeded random weights:
loss and every gradient in float32 agree to round-off, and the same
comparison fails the program run in bfloat16."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny_root  # noqa: F401  (puts the repository on the path)
from bench import weights_moe
from bench.reference_moe import MoEReference

CFG = {"name": "tiny_qwen3_moe", "hidden_size": 256, "head_dim": 64,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "moe_intermediate_size": 128, "router_experts": 8, "num_experts": 4,
       "expert_start": 4, "num_experts_per_tok": 2, "vocab_size": 512,
       "rope_theta": 1e6, "rms_norm_eps": 1e-6, "attention_bias": False,
       "tie_word_embeddings": False, "router_aux_loss_coef": 0.001}
LAYERS, ROWS, SEQ = 2, 2, 24
# float32 on both sides, products and sums in other orders: the loss
# agrees to ~1e-7 and each gradient leaf to ~1e-6 of its largest entry
LOSS_RTOL, GRAD_TOL = 2e-6, 2e-5


def program_config():
    from repro.configs import get_config

    mc = get_config("qwen3-moe-30b-a3b").reduced()
    moe = replace(mc.moe, num_experts=8, top_k=2, expert_d_ff=128,
                  num_held=4, expert_start=4)
    return replace(mc, num_layers=LAYERS, vocab_size=512, moe=moe)


@pytest.fixture(scope="module")
def setup():
    from repro.core.pipeline import (PipelineConfig, make_stage_mesh,
                                     pipeline_step_fn)

    mc = program_config()
    params = weights_moe.make_params(CFG, LAYERS, 7, jnp.float32)
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, 512, (ROWS, SEQ)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 512, (ROWS, SEQ)), jnp.int32)
    ref_loss, ref_grad = jax.jit(MoEReference(CFG).loss_and_grad,
                                 static_argnums=(3,))(
        weights_moe.flatten(params), tokens, labels, "f32")

    def program(dtype):
        step = pipeline_step_fn(mc, make_stage_mesh(1), (LAYERS,), ROWS,
                                pipe=PipelineConfig(compute_dtype=dtype))
        loss, grads, rows = jax.jit(step)(params, tokens, labels)
        return float(loss), weights_moe.flatten(grads), rows

    return float(ref_loss), ref_grad, program


def worst(prog, ref):
    return max(float(jnp.max(jnp.abs(prog[k] - ref[k])))
               / max(float(jnp.max(jnp.abs(ref[k]))), 1e-30) for k in ref)


def test_program_matches_reference_in_float32(setup):
    ref_loss, ref_grad, program = setup
    loss, grads, rows = program("float32")
    assert abs(loss - ref_loss) <= LOSS_RTOL * abs(ref_loss), (loss, ref_loss)
    assert worst(grads, ref_grad) <= GRAD_TOL, worst(grads, ref_grad)
    # the held experts' rows: at most every (token, choice) of each layer
    assert rows.shape == (LAYERS, 4)
    assert 0 < int(rows.sum()) < LAYERS * ROWS * SEQ * 2


def test_bfloat16_program_fails_the_tolerances(setup):
    ref_loss, ref_grad, program = setup
    loss, grads, _ = program("bfloat16")
    assert (abs(loss - ref_loss) > LOSS_RTOL * abs(ref_loss)
            and worst(grads, ref_grad) > GRAD_TOL)
