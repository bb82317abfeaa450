"""Weights from the seed, made by the benchmark in the layout the program
takes (a dense decoder: period-1 layer stack under ``slots``).

One jitted call draws every leaf on the device in the type it is served
or trained in. The reference gets these same arrays, never anything the
program made; it can also draw them again from the seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def jax_seed(seed: int) -> int:
    """A 31-bit key for JAX from any whole-number seed (the driver's seeds
    are larger than 32 signed bits hold)."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def leaf_specs(cfg: dict, layers: int) -> dict:
    """path -> (shape, kind, scale). ``kind`` is ``normal`` (scale * N(0,1))
    or ``norm`` (1 + scale * N(0,1))."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    s = {
        "embed": ((v, d), "normal", 0.02),
        "final_norm": ((d,), "norm", 0.1),
        "norm1": ((layers, d), "norm", 0.1),
        "norm2": ((layers, d), "norm", 0.1),
        "wq": ((layers, d, h * hd), "normal", 1 / math.sqrt(d)),
        "wk": ((layers, d, kh * hd), "normal", 1 / math.sqrt(d)),
        "wv": ((layers, d, kh * hd), "normal", 1 / math.sqrt(d)),
        "wo": ((layers, h * hd, d), "normal", 1 / math.sqrt(h * hd)),
        "w_gate": ((layers, d, f), "normal", 1 / math.sqrt(d)),
        "w_up": ((layers, d, f), "normal", 1 / math.sqrt(d)),
        "w_down": ((layers, f, d), "normal", 1 / math.sqrt(f)),
    }
    if cfg["attention_bias"]:
        s.update({"bq": ((layers, h * hd), "normal", 0.1),
                  "bk": ((layers, kh * hd), "normal", 0.1),
                  "bv": ((layers, kh * hd), "normal", 0.1)})
    if not cfg["tie_word_embeddings"]:
        s["lm_head"] = ((d, v), "normal", 0.02)
    return s


def _draw(key, spec, dtype):
    shape, kind, scale = spec
    x = scale * jax.random.normal(key, shape, jnp.float32)
    return (1.0 + x if kind == "norm" else x).astype(dtype)


def make_params(cfg: dict, layers: int, seed: int, dtype) -> dict:
    """The program's parameter pytree, drawn on the device in one call."""
    specs = leaf_specs(cfg, layers)
    names = sorted(specs)

    @jax.jit
    def build(key):
        flat = {n: _draw(jax.random.fold_in(key, i), specs[n], dtype)
                for i, n in enumerate(names)}
        return nest(flat)

    return build(jax.random.PRNGKey(jax_seed(seed)))


def nest(flat: dict) -> dict:
    """Flat leaf names -> the program's nesting."""
    attn = {k: flat[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in flat}
    mlp = {k: flat[k] for k in ("w_gate", "w_up", "w_down")}
    out = {"embed": flat["embed"], "final_norm": flat["final_norm"],
           "slots": ({"norm1": flat["norm1"], "attn": attn,
                      "norm2": flat["norm2"], "mlp": mlp},)}
    if "lm_head" in flat:
        out["lm_head"] = flat["lm_head"]
    return out


def flatten(params: dict) -> dict:
    """The program's nesting -> flat leaf names (inverse of :func:`nest`)."""
    slot = params["slots"][0]
    flat = {"embed": params["embed"], "final_norm": params["final_norm"],
            "norm1": slot["norm1"], "norm2": slot["norm2"],
            **slot["attn"], **slot["mlp"]}
    if "lm_head" in params:
        flat["lm_head"] = params["lm_head"]
    return flat
