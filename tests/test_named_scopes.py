"""The device scopes the benchmark's per-layer metrics read are in the
compiled programs' ``op_name`` metadata: the engine step (with the
model's cache writes) and the 1F1B executor step followed by AdamW."""
import re

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.pipeline import PipelineConfig, make_stage_mesh, \
    pipeline_step_fn
from repro.models.model import init_params
from repro.optim.optimizers import adamw, apply_updates
from repro.serving import ServeConfig, ServingService

ENGINE_SCOPES = {"engine.admit", "engine.prefill", "engine.decode",
                 "engine.sample", "model.layers", "model.block",
                 "model.kv_write"}
TRAIN_SCOPES = {"pipeline.step", "pipeline.fwd", "pipeline.bwd",
                "pipeline.head", "pipeline.accum", "pipeline.hop",
                "optim.clip", "optim.adamw", "optim.apply"}


def _scopes(compiled_text: str) -> set:
    """Every non-final component of every ``op_name`` path."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', compiled_text):
        for part in name.split("/")[:-1]:
            out.update(re.findall(r"[a-z]+\.[a-z_]+", part))
    return out


def test_engine_step_scopes():
    svc = ServingService(ServeConfig(num_layers=2, num_slots=2,
                                     arrival_slots=1, prompt_pad=8,
                                     max_new=4, decode_chunk=2))
    arr = (jnp.zeros((1, 8), jnp.int32), jnp.ones((1,), jnp.int32),
           jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
           jnp.int32(1))
    text = svc._jstep.lower(svc.params, svc.state, *arr).compile().as_text()
    assert ENGINE_SCOPES <= _scopes(text)
    # sampling and cache writes sit inside prefill and decode
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("engine.decode" in n and "engine.sample" in n for n in names)
    assert any("engine.prefill" in n and "model.kv_write" in n
               for n in names)
    # the layer loop's own work, under model.layers and outside
    # model.block, slices each layer's weights; the caches ride in its
    # carry and are written in place under model.kv_write, never stacked
    own = {n.rsplit("/", 1)[1] for n in names
           if "engine.decode" in n and "model.layers" in n
           and "model.block" not in n}
    assert "dynamic_slice" in own and "dynamic_update_slice" not in own
    assert any("engine.decode" in n and "model.kv_write" in n
               for n in names)
    # (a reduction's own computation carries a bare path: skip those)
    assert all("model.layers" in n for n in names
               if "model.block" in n and n.startswith("jit("))


def test_train_step_scopes():
    cfg = get_config("qwen2.5-3b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    step_fn = pipeline_step_fn(cfg, make_stage_mesh(1), (2,), 2,
                               pipe=PipelineConfig(compute_dtype="float32"))
    opt = adamw(1e-3, max_grad_norm=1.0)

    def step(p, o, tokens, labels):
        loss, grads = step_fn(p, tokens, labels)
        ups, o = opt.update(grads, o, p)
        return apply_updates(p, ups), o, loss

    tok = jnp.zeros((2, 16), jnp.int32)
    text = jax.jit(step).lower(params, opt.init(params), tok,
                               tok).compile().as_text()
    assert TRAIN_SCOPES <= _scopes(text)
