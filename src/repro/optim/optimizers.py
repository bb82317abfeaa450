"""Minimal optimizer library (optax is not available offline).

Optimizers are (init, update) pairs over arbitrary pytrees, matching the
usual gradient-transformation contract:

    opt = adamw(lr=1e-3)
    state = opt.init(params)
    updates, state = opt.update(grads, state, params)
    params = apply_updates(params, updates)

Named scopes label their device time: ``optim.clip``
(``clip_by_global_norm``), ``optim.adamw`` (the moment and update maps)
and ``optim.apply`` (``apply_updates``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class OptState(NamedTuple):
    step: jax.Array
    mu: Any = None
    nu: Any = None


@dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable


def _tree_zeros_like(tree, dtype=None):
    return jax.tree.map(lambda x: jnp.zeros_like(x, dtype=dtype or x.dtype), tree)


def apply_updates(params, updates):
    with jax.named_scope("optim.apply"):
        return jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params,
                            updates)


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def clip_by_global_norm(tree, max_norm: float):
    with jax.named_scope("optim.clip"):
        norm = global_norm(tree)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
        return jax.tree.map(lambda x: x * scale.astype(x.dtype), tree), norm


def cosine_schedule(base_lr: float, total_steps: int, final_frac: float = 0.1):
    def lr(step):
        frac = jnp.clip(step / max(total_steps, 1), 0.0, 1.0)
        cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
        return base_lr * (final_frac + (1 - final_frac) * cos)

    return lr


def linear_warmup_cosine(base_lr: float, warmup: int, total_steps: int, final_frac: float = 0.1):
    cos = cosine_schedule(base_lr, max(total_steps - warmup, 1), final_frac)

    def lr(step):
        w = jnp.clip(step / max(warmup, 1), 0.0, 1.0)
        return jnp.where(step < warmup, base_lr * w, cos(step - warmup))

    return lr


def adamw(
    lr: float | Callable = 1e-3,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    max_grad_norm: Optional[float] = None,
    state_dtype=jnp.float32,
) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        return OptState(
            step=jnp.zeros((), jnp.int32),
            mu=_tree_zeros_like(params, state_dtype),
            nu=_tree_zeros_like(params, state_dtype),
        )

    def update(grads, state: OptState, params=None):
        if max_grad_norm is not None:
            grads, _ = clip_by_global_norm(grads, max_grad_norm)
        with jax.named_scope("optim.adamw"):
            step = state.step + 1
            stepf = step.astype(jnp.float32)
            bc1 = 1 - b1**stepf
            bc2 = 1 - b2**stepf
            mu = jax.tree.map(
                lambda m, g: b1 * m + (1 - b1) * g.astype(m.dtype),
                state.mu,
                grads,
            )
            nu = jax.tree.map(
                lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(v.dtype)),
                state.nu,
                grads,
            )
            lr_t = lr_fn(step)

            def upd(m, v, p):
                u = -(lr_t * (m / bc1) / (jnp.sqrt(v / bc2) + eps))
                if weight_decay:
                    u = u - lr_t * weight_decay * p.astype(u.dtype)
                return u.astype(p.dtype)

            updates = jax.tree.map(upd, mu, nu, params)
        return updates, OptState(step=step, mu=mu, nu=nu)

    return Optimizer(init=init, update=update)


def sgd_momentum(lr: float | Callable = 1e-2, momentum: float = 0.9) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        return OptState(step=jnp.zeros((), jnp.int32), mu=_tree_zeros_like(params))

    def update(grads, state: OptState, params=None):
        step = state.step + 1
        mu = jax.tree.map(lambda m, g: momentum * m + g.astype(m.dtype), state.mu, grads)
        updates = jax.tree.map(lambda m, p: (-lr_fn(step) * m).astype(p.dtype), mu, params)
        return updates, OptState(step=step, mu=mu)

    return Optimizer(init=init, update=update)
