"""The plain reference: a dense decoder written from the configuration's
equations in float32, with no cache, batching, kernels or program code.

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * norm1
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv   (bias if stated)
                q, k = rope(q), rope(k)     (rotate-half, theta, full head)
                x += softmax(q k^T / sqrt(hd) + causal) v  Wo  (GQA groups)
                h = rmsnorm(x) * norm2
                x += (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x) * final_norm  @  (embed^T if tied else lm_head)

Every matrix product runs at ``Precision.HIGHEST``. ``prec="fp8"`` is the
control: each product's two inputs are rounded to float8 e4m3 with one
absmax scale per tensor (their gradients to e5m2, scaled alike), the step
below the bfloat16 the configurations state. The reference runs layer by
layer (one ``lax.scan``) over blocks of rows, so it fits beside the
weights once the program's state is gone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round(x, F8, F8_MAX)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    # gradients go through float8 e5m2, with a scale of their own
    return (_round(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _q(x, prec: str):
    if prec == "f32":
        return x
    if prec != "fp8":
        raise ValueError(f"unknown precision {prec!r}")
    return _fp8(x)


def mm(eq: str, a, b, prec: str):
    return jnp.einsum(eq, _q(a, prec), _q(b, prec), precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (R, T, H, hd); pos: (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


class Reference:
    """Functions of one configuration (its file's dict)."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.h = cfg["num_attention_heads"]
        self.kh = cfg["num_key_value_heads"]
        self.hd = cfg["head_dim"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.bias = cfg["attention_bias"]
        self.tied = cfg["tie_word_embeddings"]

    # -- forward --------------------------------------------------------
    def layer(self, x, p, prec):
        r, t, _ = x.shape
        f32 = lambda a: a.astype(jnp.float32)
        h = rms_norm(x, f32(p["norm1"]), self.eps)
        q = mm("rtd,de->rte", h, f32(p["wq"]), prec)
        k = mm("rtd,de->rte", h, f32(p["wk"]), prec)
        v = mm("rtd,de->rte", h, f32(p["wv"]), prec)
        if self.bias:
            q, k, v = q + f32(p["bq"]), k + f32(p["bk"]), v + f32(p["bv"])
        pos = jnp.arange(t)
        q = rope(q.reshape(r, t, self.h, self.hd), pos, self.theta)
        k = rope(k.reshape(r, t, self.kh, self.hd), pos, self.theta)
        v = v.reshape(r, t, self.kh, self.hd)
        g = self.h // self.kh
        q = q.reshape(r, t, self.kh, g, self.hd)
        s = mm("rqkgd,rskd->rkgqs", q, k, prec) / jnp.sqrt(float(self.hd))
        causal = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = mm("rkgqs,rskd->rqkgd", w, v, prec).reshape(r, t, -1)
        x = x + mm("rte,ed->rtd", o, f32(p["wo"]), prec)
        h = rms_norm(x, f32(p["norm2"]), self.eps)
        a = jax.nn.silu(mm("rtd,df->rtf", h, f32(p["w_gate"]), prec))
        a = a * mm("rtd,df->rtf", h, f32(p["w_up"]), prec)
        return x + mm("rtf,fd->rtd", a, f32(p["w_down"]), prec)

    def hidden(self, flat, tokens, prec="f32"):
        """(R, T) tokens -> (R, T, d) final-normed hidden states."""
        x = flat["embed"].astype(jnp.float32)[tokens]
        names = ("norm1", "norm2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                 "w_down") + (("bq", "bk", "bv") if self.bias else ())
        stack = {n: flat[n] for n in names}
        x, _ = jax.lax.scan(lambda c, p: (self.layer(c, p, prec), None),
                            x, stack)
        return rms_norm(x, flat["final_norm"].astype(jnp.float32), self.eps)

    def head(self, flat):
        w = flat["embed"].T if self.tied else flat["lm_head"]
        return w.astype(jnp.float32)

    def logits(self, flat, hs, prec="f32"):
        return mm("...d,dv->...v", hs, self.head(flat), prec)

    # -- serving: gaps of served tokens ---------------------------------
    def served_gaps(self, flat, tokens, at, gold, control: bool):
        """Rows of prompt + served tokens. At positions ``at`` (R, K) the
        reference's best logit minus its logit of ``gold`` (R, K); with
        ``control``, also minus its logit of the token the fp8 control
        puts first there."""
        def pick(hs):
            return jnp.take_along_axis(hs, at[..., None], axis=1)

        lg = self.logits(flat, pick(self.hidden(flat, tokens)))
        best = lg.max(-1)
        gap = best - jnp.take_along_axis(lg, gold[..., None], -1)[..., 0]
        if not control:
            return gap, jnp.zeros_like(gap)
        lc = self.logits(flat, pick(self.hidden(flat, tokens, "fp8")), "fp8")
        first = lc.argmax(-1)
        cgap = best - jnp.take_along_axis(lg, first[..., None], -1)[..., 0]
        return gap, cgap

    # -- training: loss, gradients, AdamW -------------------------------
    def loss(self, flat, tokens, labels, prec="f32"):
        lg = self.logits(flat, self.hidden(flat, tokens, prec), prec)
        logz = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
        return jnp.mean(logz - gold)

    def loss_and_grad(self, flat, tokens, labels, prec="f32"):
        """Mean loss over all rows and its gradient, one row at a time."""
        vg = jax.value_and_grad(self.loss)

        def body(acc, row):
            l, g = vg(flat, row[0][None], row[1][None], prec)
            return jax.tree.map(jnp.add, acc, (l, g)), None

        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, flat))
        (l, g), _ = jax.lax.scan(body, zero, (tokens, labels))
        n = tokens.shape[0]
        return l / n, jax.tree.map(lambda a: a / n, g)


def adamw_step(p, m, v, g, step, opt: dict):
    """One AdamW update (Loshchilov & Hutter) after clipping the gradient to
    global norm ``max_grad_norm``; ``step`` counts from 1."""
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    clip = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-9))
    g = jax.tree.map(lambda x: x * clip, g)
    b1, b2, lr = opt["b1"], opt["b2"], opt["lr"]
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(x, a, b):
        u = -lr * (a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
        return x + u - lr * opt["weight_decay"] * x

    return jax.tree.map(upd, p, m, v), m, v, g
