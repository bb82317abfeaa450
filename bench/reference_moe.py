"""The plain reference of a Qwen3-MoE decoder on one expert share, written
from the configuration's equations in float32, with no kernels, cache,
batching or program code. x is (T, d):

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * norm1
                q, k, v = h Wq, h Wk, h Wv                 (no bias)
                q, k = rmsnorm_head(q) * q_norm, rmsnorm_head(k) * k_norm
                                                 (per head, over head_dim)
                q, k = rope(q), rope(k)      (rotate-half, theta, full head)
                x += softmax(q k^T / sqrt(hd) + causal) v  Wo  (GQA groups)
                h2 = rmsnorm(x) * norm2
                p = softmax(h2 Wr)              (all router_experts outputs)
                (w, e) = top_k(p);  w = w / sum(w)
                x += sum_{j: e_j held}  w_j (silu(h2 Wg[e_j]) * (h2 Wu[e_j])) Wd[e_j]
                aux_l = E * sum_e (tokens whose k choices hold e, over T) * mean_t p_e
    logits = rmsnorm(x) * final_norm  @  lm_head      (the vocabulary slice)
    loss = mean_t CE  +  router_aux_loss_coef * mean_l aux_l

Each held expert is applied to every token and masked by the routing
weights (no dispatch). Departures from HF's ``Qwen3MoeForCausalLM``:

* only the held experts (``num_experts`` from ``expert_start``) are
  applied; the router still scores all ``router_experts`` and the gates
  are renormalised over all k choices, so this is one share's part of
  the layer, as the deployment's chip computes it;
* the vocabulary is a slice: embedding, head, logits and loss are over
  its ``vocab_size`` rows;
* the aux loss: HF's ``load_balancing_loss_func`` concatenates every
  layer's router logits and takes both means over all layers' tokens at
  once; here each layer's product (the same k-summed form) is taken over
  its own tokens and the layers are averaged;
* no dropout, attention mask or sliding window (the config has none in
  use), and the loss is over every position of a row.

Every matrix product runs at ``Precision.HIGHEST``, the router's too;
``prec="fp8"`` is ``bench.reference``'s control. Attention runs in
blocks of queries, each over all keys, and each layer is rematerialised
in the backward pass, so an 8k-token row fits beside the weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import Reference, mm, rms_norm, rope

Q_BLOCK = 512  # queries per attention block


class MoEReference(Reference):
    """Functions of one Qwen3-MoE configuration (its file's dict)."""

    def __init__(self, cfg: dict):
        super().__init__(cfg)
        self.e = cfg["router_experts"]
        self.k = cfg["num_experts_per_tok"]
        self.held = cfg["num_experts"]
        self.start = cfg["expert_start"]
        self.aux_w = cfg["router_aux_loss_coef"]

    def attention(self, q, k, v, prec):
        """q (R, T, KH, G, hd), k and v (R, T, KH, hd) -> (R, T, KH*G*hd),
        causal, one block of queries at a time."""
        r, t = q.shape[:2]
        qb = min(Q_BLOCK, t)
        n = -(-t // qb)
        q = jnp.pad(q, ((0, 0), (0, n * qb - t)) + ((0, 0),) * 3)
        q = jnp.moveaxis(q.reshape((r, n, qb) + q.shape[2:]), 1, 0)

        @jax.checkpoint
        def block(args):
            qi, i = args
            s = mm("rqkgd,rskd->rkgqs", qi, k, prec) / math.sqrt(self.hd)
            causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(t)
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return mm("rkgqs,rskd->rqkgd", w, v, prec)

        o = jax.lax.map(block, (q, jnp.arange(n)))
        return jnp.moveaxis(o, 0, 1).reshape(r, n * qb, -1)[:, :t]

    def experts(self, h, p, prec):
        """h (R, T, d) -> (the held experts' part of the layer, aux_l)."""
        logits = mm("rtd,de->rte", h, p["router"], prec)
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, self.k)               # (R, T, k)
        w = w / jnp.sum(w, -1, keepdims=True)
        onehot = jax.nn.one_hot(ids, self.e, dtype=jnp.float32)
        share = jnp.mean(jnp.sum(onehot, 2), axis=1)        # (R, E)
        aux = self.e * jnp.sum(share * jnp.mean(probs, axis=1), -1)
        # routing weight of each held expert for each token
        c = jnp.einsum("rtk,rtke->rte", w,
                       onehot[..., self.start:self.start + self.held])

        def one(y, j):
            g = mm("rtd,df->rtf", h, p["w_gate"][j], prec)
            u = mm("rtd,df->rtf", h, p["w_up"][j], prec)
            out = mm("rtf,fd->rtd", jax.nn.silu(g) * u, p["w_down"][j], prec)
            return y + c[..., j, None] * out, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(self.held))
        return y, jnp.mean(aux)

    def layer(self, x, p, prec):
        """One block; returns (x, aux_l)."""
        r, t, _ = x.shape
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        h = rms_norm(x, p["norm1"], self.eps)
        q = mm("rtd,de->rte", h, p["wq"], prec)
        k = mm("rtd,de->rte", h, p["wk"], prec)
        v = mm("rtd,de->rte", h, p["wv"], prec)
        q = rms_norm(q.reshape(r, t, self.h, self.hd), p["q_norm"], self.eps)
        k = rms_norm(k.reshape(r, t, self.kh, self.hd), p["k_norm"], self.eps)
        pos = jnp.arange(t)
        q, k = rope(q, pos, self.theta), rope(k, pos, self.theta)
        v = v.reshape(r, t, self.kh, self.hd)
        q = q.reshape(r, t, self.kh, self.h // self.kh, self.hd)
        x = x + mm("rte,ed->rtd", self.attention(q, k, v, prec), p["wo"],
                   prec)
        y, aux = self.experts(rms_norm(x, p["norm2"], self.eps), p, prec)
        return x + y, aux

    NAMES = ("norm1", "norm2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
             "router", "w_gate", "w_up", "w_down")

    def hidden_aux(self, flat, tokens, prec="f32"):
        """(R, T) tokens -> ((R, T, d) final-normed hidden states, the
        layers' mean aux loss)."""
        x = flat["embed"].astype(jnp.float32)[tokens]
        stack = {n: flat[n] for n in self.NAMES}
        layer = jax.checkpoint(lambda c, p: self.layer(c, p, prec))
        x, aux = jax.lax.scan(layer, x, stack)
        x = rms_norm(x, flat["final_norm"].astype(jnp.float32), self.eps)
        return x, jnp.mean(aux)

    def hidden(self, flat, tokens, prec="f32"):
        return self.hidden_aux(flat, tokens, prec)[0]

    def loss(self, flat, tokens, labels, prec="f32"):
        hs, aux = self.hidden_aux(flat, tokens, prec)
        lg = self.logits(flat, hs, prec)
        logz = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
        return jnp.mean(logz - gold) + self.aux_w * aux
