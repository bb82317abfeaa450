"""Expert-parallel MoE via explicit shard_map all-to-all.

GSPMD lowers the scatter-based MoE dispatch to f32 ALL-REDUCES of the full
(B, S*topk, D) buffers (measured: 5.6 TB/step on qwen3-235b train_4k,
97% of all collective bytes). The communication-optimal schedule is the
GShard/DeepSpeed one: route token copies to their experts' home shards
with `jax.lax.all_to_all`, run the expert FFN locally, route back, and
combine locally. This module implements exactly that under `shard_map`:

  per device:  local tokens -(scatter, local)-> (tp, E_loc*C, D)
               -- all_to_all over the TP axis -->
               (tp, E_loc*C, D) grouped by my experts -> FFN ->
               -- all_to_all back --> local combine with gates.

Cross-shard traffic per layer: 2 x (E, C_local, D) in activation dtype,
instead of ~2 x (B, S*topk, D) f32 all-reduce. Both all_to_alls are
linear, so JAX autodiff transposes them back to all_to_alls - the backward
pass gets the same schedule for free.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distribution import context as ctx
from repro.models.layers import activation_fn


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    return max(int(math.ceil(tokens * m.top_k * m.capacity_factor / m.num_experts)), 1)


def moe_apply_a2a(params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for layers.moe_apply when the activation-sharding context is
    installed and the TP axis divides num_experts. x: (B, S, D)."""
    mesh = ctx._STATE["mesh"]
    batch_ax = ctx._STATE["batch"]
    model_ax = ctx._STATE["model"]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get(model_ax, 1)
    m = cfg.moe
    e, k = m.num_experts, m.top_k
    e_loc = e // tp
    swiglu = cfg.activation == "swiglu"

    def local(xl, router, wg, wu, wd):
        # xl: (B_loc, S, D) - same tokens on every model shard within a
        # data shard. wg/wu/wd: (E_loc, D, F) local experts.
        b, s, d = xl.shape
        toks = b * s
        c = _capacity(toks, cfg)
        xt = xl.reshape(toks, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, -1)
        gates, ids = jax.lax.top_k(probs, k)  # (T, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        # position of each (token, choice) within its expert
        flat = ids.reshape(-1)
        onehot = jax.nn.one_hot(flat, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0].reshape(toks, k)
        keep = pos < c
        # destination layout: shard = id // e_loc, row = (id % e_loc) * c + pos
        slot = ids * c + jnp.minimum(pos, c - 1)  # global (E*C) slot

        buf = jnp.zeros((e * c, d), xl.dtype)
        for j in range(k):
            buf = buf.at[slot[:, j]].add(xt * keep[:, j, None].astype(xl.dtype))
        buf = buf.reshape(tp, e_loc * c, d)
        # exchange: device p receives every shard's block for ITS experts
        recv = jax.lax.all_to_all(buf, model_ax, split_axis=0, concat_axis=0)
        # name the a2a results so the layer remat policy can SAVE them:
        # recomputing the forward under remat would re-run both exchanges
        recv = jax.ad_checkpoint.checkpoint_name(recv, "moe_a2a")
        # (tp, e_loc*c, d): entry [src] = tokens from shard src for my experts
        recv = recv.reshape(tp, e_loc, c, d).transpose(1, 0, 2, 3)
        recv = recv.reshape(e_loc, tp * c, d)

        if swiglu:
            g = jnp.einsum("ekd,edf->ekf", recv, wg.astype(xl.dtype))
            u = jnp.einsum("ekd,edf->ekf", recv, wu.astype(xl.dtype))
            h = jax.nn.silu(g) * u
        else:
            u = jnp.einsum("ekd,edf->ekf", recv, wu.astype(xl.dtype))
            h = activation_fn(cfg.activation)(u)
        out = jnp.einsum("ekf,efd->ekd", h, wd.astype(xl.dtype))

        out = out.reshape(e_loc, tp, c, d).transpose(1, 0, 2, 3)  # (tp, e_loc, c, d)
        back = jax.lax.all_to_all(
            out.reshape(tp, e_loc * c, d), model_ax, split_axis=0, concat_axis=0
        )
        back = jax.ad_checkpoint.checkpoint_name(back, "moe_a2a")
        back = back.reshape(e * c, d)  # my tokens' results, global slot layout

        got = back[slot.reshape(-1)].reshape(toks, k, d)
        w = (gates * keep).astype(xl.dtype)
        y = jnp.einsum("tkd,tk->td", got, w).reshape(b, s, d)

        # load-balance aux (Switch), averaged over the data axes
        f_e = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
        p_e = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(f_e * p_e) * m.router_aux_weight
        if batch_ax:
            aux = jax.lax.pmean(aux, batch_ax)
        return y, aux

    xspec = P(batch_ax, None, None)
    wspec = P(model_ax, None, None)
    y, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(xspec, P(), wspec, wspec, wspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(
        x,
        params["router"],
        params.get("w_gate", params["w_up"]),
        params["w_up"],
        params["w_down"],
    )
    return y, aux


def a2a_applicable(cfg: ModelConfig) -> bool:
    if not ctx.active() or not cfg.moe.enabled:
        return False
    mesh = ctx._STATE["mesh"]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get(ctx._STATE["model"], 1)
    return tp > 1 and cfg.moe.num_experts % tp == 0
