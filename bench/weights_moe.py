"""Weights from the seed for a Qwen3-MoE configuration, in the layout the
program takes: a period-1 stack of blocks under ``slots``, each with
QK-norm attention and an expert layer holding ``num_experts`` experts
(the share held here) behind a router over ``router_experts``.

As in ``bench.weights``, one jitted call draws every leaf on the device;
the reference gets these same arrays, or draws them again from the seed.
"""
from __future__ import annotations

import math

import jax

from bench.weights import _draw, jax_seed

ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
MOE = ("router", "w_gate", "w_up", "w_down")


def leaf_specs(cfg: dict, layers: int) -> dict:
    """path -> (shape, kind, scale), as ``bench.weights.leaf_specs``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    e, held, f = (cfg["router_experts"], cfg["num_experts"],
                  cfg["moe_intermediate_size"])
    return {
        # unit RMS, so that the token, not the attention's average over the
        # prefix, decides the route (the configuration file's `assumed`)
        "embed": ((v, d), "normal", 1.0),
        "lm_head": ((d, v), "normal", 0.02),
        "final_norm": ((d,), "norm", 0.1),
        "norm1": ((layers, d), "norm", 0.1),
        "norm2": ((layers, d), "norm", 0.1),
        "wq": ((layers, d, h * hd), "normal", 1 / math.sqrt(d)),
        "wk": ((layers, d, kh * hd), "normal", 1 / math.sqrt(d)),
        "wv": ((layers, d, kh * hd), "normal", 1 / math.sqrt(d)),
        "wo": ((layers, h * hd, d), "normal", 1 / math.sqrt(h * hd)),
        "q_norm": ((layers, hd), "norm", 0.1),
        "k_norm": ((layers, hd), "norm", 0.1),
        "router": ((layers, d, e), "normal", 1 / math.sqrt(d)),
        "w_gate": ((layers, held, d, f), "normal", 1 / math.sqrt(d)),
        "w_up": ((layers, held, d, f), "normal", 1 / math.sqrt(d)),
        "w_down": ((layers, held, f, d), "normal", 1 / math.sqrt(f)),
    }


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
    return {"embed": flat["embed"], "lm_head": flat["lm_head"],
            "final_norm": flat["final_norm"],
            "slots": ({"norm1": flat["norm1"], "norm2": flat["norm2"],
                       "attn": {k: flat[k] for k in ATTN},
                       "moe": {k: flat[k] for k in MOE}},)}


def flatten(params: dict) -> dict:
    """The program's nesting -> flat leaf names (inverse of :func:`nest`)."""
    slot = params["slots"][0]
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"], "norm1": slot["norm1"],
            "norm2": slot["norm2"], **slot["attn"], **slot["moe"]}
