"""Synthetic data pipeline + ShapeDtypeStruct input specs.

The data pipeline is deterministic and seeded (no dataset downloads on this
box); it produces next-token LM batches plus stub modality features for
VLM/audio architectures. ``input_specs`` mirrors the exact structures as
``jax.ShapeDtypeStruct`` stand-ins for ``.lower()`` without allocation.
"""
from __future__ import annotations

from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.frontends import FRONTEND_DIMS


def _frontend_len(cfg: ModelConfig) -> int:
    return cfg.frontend_tokens if cfg.frontend != "none" else 0


def synthetic_batch(
    cfg: ModelConfig, batch: int, seq: int, seed: int = 0
) -> Dict[str, jax.Array]:
    """A train batch: tokens (B, S_text), labels shifted, optional frontend."""
    f = _frontend_len(cfg)
    s_text = seq - f
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, size=(batch, s_text + 1), dtype=np.int32)
    out = {
        "tokens": jnp.asarray(toks[:, :-1]),
        "labels": jnp.asarray(toks[:, 1:]),
    }
    if f:
        out["frontend"] = jnp.asarray(
            rng.standard_normal((batch, f, FRONTEND_DIMS[cfg.frontend]), dtype=np.float32)
        )
    return out


def synthetic_stream(
    cfg: ModelConfig, batch: int, seq: int, seed: int = 0
) -> Iterator[Dict[str, jax.Array]]:
    step = 0
    while True:
        yield synthetic_batch(cfg, batch, seq, seed=seed + step)
        step += 1


# ---------------------------------------------------------------------------
# input specs (no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this shape kind."""
    b, s = shape.global_batch, shape.seq_len
    f = _frontend_len(cfg)
    if shape.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, s - f), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s - f), jnp.int32),
        }
        if f:
            specs["frontend"] = jax.ShapeDtypeStruct(
                (b, f, FRONTEND_DIMS[cfg.frontend]), jnp.float32
            )
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s - f), jnp.int32)}
        if f:
            specs["frontend"] = jax.ShapeDtypeStruct(
                (b, f, FRONTEND_DIMS[cfg.frontend]), jnp.float32
            )
        return specs
    if shape.kind == "decode":
        return {
            "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
            "cache_index": jax.ShapeDtypeStruct((), jnp.int32),
        }
    raise KeyError(shape.kind)
