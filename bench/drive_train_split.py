"""Training path over a stage mesh: the 1F1B split executor
(``pipeline_step_fn``) over the mix's ``boundaries``, one stage per chip,
followed by AdamW, one jitted step with donated state. It drives a dense
configuration (``bench.weights``, ``bench.reference``) or a Qwen3-MoE
expert share (``bench.weights_moe``, ``bench.reference_moe``), chosen by
the configuration file: one with ``router_experts`` is an expert share.

As ``bench.drive_train`` (whose comparison, ``loss_gap``, ``grad_gap`` and
``delta_gap`` over the first three steps, this reuses): set-up drives the
step from the seed through its first three steps, the window then sends
steps ``ahead_s`` of device time ahead, and the plain float32 reference
is compared with those three steps once the window has closed. On more
than one chip every parameter, gradient and AdamW moment, the program's
and the reference's alike, is split over the chips along its last axis
that they divide (replicated where none does): 16 B a parameter of an
8-layer cut would not fit one chip.

The mix's ``tokens``: ``uniform`` (``bench.gen.batches``: independent
random tokens and labels) or ``zipf`` with exponent ``s``: ids drawn by
rank from a Zipf law over the vocabulary, as natural text is, each label
the next token of its row.

The mix's ``optimizer`` may give ``warmup_steps``: the learning rate then
rises linearly from ``lr / warmup_steps`` at step 1 to ``lr``, in the
program's AdamW and in the reference's alike.

For an expert share the step also returns the rows each layer routed to
each expert it holds; with ``--trace 1`` the window records them a step
as the counter ``moe.rows`` of a ``repro.tracing.Tracer`` (``held``: the
rows per layer, ``largest``: the largest held expert's rows per layer),
kept under the record's ``moe_rows``.
"""
from __future__ import annotations

import dataclasses
import importlib
import time
from collections import deque

import numpy as np

from bench import gen, reference, reference_moe, weights, weights_moe
from bench.common import log, peak_in_use, program_model_config
from bench.drive_train import (CHECKED_STEPS, TrainCell, _Opt, compare,
                               norms)


def moe_model_config(cfg: dict, layers: int):
    """The program's ModelConfig for an expert share; refused unless it is
    the configuration the file states, QK-norm and the share included."""
    mod = importlib.import_module(f"repro.configs.{cfg['module']}")
    try:
        moe = dataclasses.replace(mod.CONFIG.moe, num_held=cfg["num_experts"],
                                  expert_start=cfg["expert_start"])
        mc = dataclasses.replace(mod.CONFIG, num_layers=layers,
                                 vocab_size=cfg["vocab_size"], moe=moe)
        got = {"qk_norm": mc.qk_norm, "held": mc.moe.held,
               "expert_start": mc.moe.expert_start}
    except (AttributeError, TypeError) as e:
        raise SystemExit(f"bench: the program's ModelConfig has no QK-norm "
                         f"or expert share ({e}); {cfg['name']} needs both")
    want = {"qk_norm": True, "held": cfg["num_experts"],
            "expert_start": cfg["expert_start"]}
    for k, v in {
            "d_model": cfg["hidden_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "vocab_size": cfg["vocab_size"],
            "num_layers": layers, "rope_theta": cfg["rope_theta"],
            "norm_eps": cfg["rms_norm_eps"],
            "qkv_bias": cfg["attention_bias"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "activation": "swiglu", "arch_type": "moe",
            "attention_window": None}.items():
        want[k], got[k] = v, getattr(mc, k)
    for k, v in {"num_experts": cfg["router_experts"],
                 "top_k": cfg["num_experts_per_tok"],
                 "expert_d_ff": cfg["moe_intermediate_size"],
                 "router_aux_weight": cfg["router_aux_loss_coef"],
                 "moe_every": cfg["decoder_sparse_step"],
                 "dispatch": "dropless"}.items():
        want[k], got[k] = v, getattr(mc.moe, k)
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad or cfg["mlp_only_layers"]:
        raise SystemExit(f"bench: program config differs from "
                         f"{cfg['name']}.json (program, file): {bad}")
    return mc


def learning_rate(o: dict):
    """AdamW's learning rate: ``lr``, or with ``warmup_steps`` a function of
    the (1-based) step that rises linearly to ``lr`` over that many."""
    import jax.numpy as jnp

    w = o.get("warmup_steps", 0)
    return (lambda step: o["lr"] * jnp.minimum(step / w, 1.0)) if w \
        else o["lr"]


def token_batches(mix: dict, seed: int, vocab: int):
    """``pool`` batches of (rows, seq) tokens and labels from the seed."""
    tok = mix["tokens"]
    if tok["kind"] == "uniform":
        return gen.batches(mix, seed, vocab)
    if tok["kind"] != "zipf":
        raise ValueError(f"unknown token distribution {tok['kind']!r}")
    b = mix["batch"]
    p = 1.0 / np.arange(1, vocab + 1) ** tok["s"]
    rng = np.random.default_rng(seed)
    rows = rng.choice(vocab, size=(b["pool"], b["rows"], b["seq"] + 1),
                      p=p / p.sum()).astype(np.int32)
    return rows[..., :-1], rows[..., 1:]


class SplitTrainCell(TrainCell):
    def __init__(self, cfg: dict, mix: dict, seed: int, spans, hooks=None,
                 tracer=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.api import PipelineConfig, adamw, make_stage_mesh, \
            pipeline_step_fn
        from repro.optim.optimizers import apply_updates
        from repro.tracing import NULL_TRACER

        self.cfg, self.mix, self.spans = cfg, mix, spans
        self.tracer = tracer or NULL_TRACER
        self.bounds = tuple(mix["boundaries"])
        self.layers = self.bounds[-1]
        self.moe = "router_experts" in cfg
        if self.moe:
            self.W, self.Ref = weights_moe, reference_moe.MoEReference
            mc = moe_model_config(cfg, self.layers)
        else:
            self.W, self.Ref = weights, reference.Reference
            mc = program_model_config(cfg, self.layers)
        b, o = mix["batch"], mix["optimizer"]
        self.mesh = make_stage_mesh(len(self.bounds))
        self.devices = list(self.mesh.devices.flat)
        n = len(self.devices)

        def split(x):
            axes = [a for a in range(x.ndim) if x.shape[a] % n == 0]
            spec = [None] * x.ndim
            if n > 1 and axes:
                spec[axes[-1]] = "stage"
            return NamedSharding(self.mesh, P(*spec))

        self.split = split
        step_fn = pipeline_step_fn(mc, self.mesh, self.bounds,
                                   b["microbatches"], pipe=PipelineConfig())
        self.opt = adamw(learning_rate(o), b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"],
                         max_grad_norm=o["max_grad_norm"])

        def train_step(params, opt_state, tokens, labels):
            # the third output is the step's report: its loss and, for an
            # expert share, the rows routed to each held expert by layer
            loss, grads, *rows = step_fn(params, tokens, labels)
            ups, opt_state = self.opt.update(grads, opt_state, params)
            return (apply_updates(params, ups), opt_state,
                    (loss, rows[0] if rows else None))

        if hooks and "train_step" in hooks:
            train_step = hooks["train_step"](train_step)
        self.step = jax.jit(train_step, donate_argnums=(0, 1))
        self.i = 0
        self.reseed(seed)

    def compiled_bytes(self) -> int:
        """The step's peak on a chip by its compiler (``memory_analysis``'s
        sum of arguments, outputs and temporaries counts buffers that
        never live at once twice)."""
        ma = self.step.lower(self.params, self.opt_state,
                             *self.batches[0]).compile().memory_analysis()
        return int(getattr(ma, "peak_memory_in_bytes", 0)) or super() \
            .compiled_bytes()

    def place(self, tree):
        import jax

        if len(self.devices) == 1:
            return tree
        return jax.tree.map(lambda x: jax.device_put(x, self.split(x)), tree)

    def init_opt(self, params):
        """AdamW's state, split as the parameters are (left to itself, the
        compiler puts its moments whole on the first chip)."""
        import jax

        if len(self.devices) == 1:
            return jax.jit(self.opt.init)(params)
        shapes = jax.eval_shape(self.opt.init, params)
        return jax.jit(self.opt.init, out_shardings=jax.tree.map(
            self.split, shapes))(params)

    def reseed(self, seed: int) -> None:
        import jax
        import jax.numpy as jnp

        self.seed, self.i = seed, 0
        self.params = self.opt_state = None
        self.params = self.place(self.W.make_params(
            self.cfg, self.layers, seed, jnp.float32))
        self.opt_state = self.init_opt(self.params)
        tok, lab = token_batches(self.mix, seed, self.cfg["vocab_size"])
        self.host_batches = (tok, lab)
        self.batches = [(jnp.asarray(t), jnp.asarray(l))
                        for t, l in zip(tok, lab)]

    def send(self):
        """Dispatch the next step; returns its (loss, rows), not yet on
        the host."""
        tokens, labels = self.batches[self.i % len(self.batches)]
        self.params, self.opt_state, report = self.step(
            self.params, self.opt_state, tokens, labels)
        self.i += 1
        return report

    def one_step(self) -> float:
        with self.spans.span("bench.step", index=self.i):
            return float(self.send()[0])

    def first_steps(self) -> dict:
        """Steps 1..3 through the window's own call; the readings the
        reference is compared with."""
        import jax
        import jax.numpy as jnp

        b1 = self.mix["optimizer"]["b1"]
        losses = [self.one_step()]
        grad = {k: v / (1 - b1) for k, v in
                norms(self.W.flatten(self.opt_state.mu)).items()}
        losses += [self.one_step() for _ in range(CHECKED_STEPS - 1)]
        p0 = self.place(self.W.make_params(self.cfg, self.layers, self.seed,
                                           jnp.float32))
        delta = norms(self.W.flatten(
            jax.tree.map(jnp.subtract, self.params, p0)))
        del p0
        return {"losses": losses, "grad": grad, "delta": delta}

    def window(self, seconds: float, tick=lambda: None) -> dict:
        """``TrainCell.window``; with a tracer on, each waited step's
        routed rows are recorded as the counter ``moe.rows``."""
        rows, seq = self.mix["batch"]["rows"], self.mix["batch"]["seq"]
        depth = self.depth()
        pending, done = deque(), []

        def wait():
            i, (loss, moe_rows) = pending.popleft()
            with self.spans.span("bench.wait", index=i):
                float(loss)
            done.append(time.perf_counter())
            if moe_rows is not None and self.tracer.enabled:
                r = np.asarray(moe_rows)
                self.tracer.count("moe.rows", r.sum(1).tolist(), step=i,
                                  largest=r.max(1).tolist())

        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            tick()
            with self.spans.span("bench.send", index=self.i):
                pending.append((self.i, self.send()))
            n += 1
            if len(pending) > depth:
                wait()
        while pending:
            wait()
        t1 = time.perf_counter()
        intervals = [{"t0": a, "t1": b} for a, b in zip([t0] + done, done)]
        return {"steps": n, "tokens": n * rows * seq, "t0": t0, "t1": t1,
                "depth": depth, "intervals": intervals}

    def free_program_state(self) -> int:
        peak = peak_in_use(self.devices)
        self.params = self.opt_state = self.batches = None
        return peak

    def reference(self, prec: str = "f32") -> dict:
        """The first three steps of the plain reference from the same seed,
        weights and batches: losses, first clipped gradient norms, change
        norms."""
        import jax
        import jax.numpy as jnp

        ref = self.Ref(self.cfg)
        o = self.mix["optimizer"]

        def init():
            return self.place(self.W.flatten(self.W.make_params(
                self.cfg, self.layers, self.seed, jnp.float32)))

        p = init()
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        kw = {}
        if len(self.devices) > 1:  # gradients and state stay split
            ps = jax.tree.map(self.split, p)
            kw = {"out_shardings": (self.split(jnp.zeros(())), ps)}
        lg = jax.jit(ref.loss_and_grad, static_argnums=(3,), **kw)
        if kw:
            kw = {"out_shardings": (ps, ps, ps, ps)}
        upd = jax.jit(reference.adamw_step, static_argnums=(5,),
                      donate_argnums=(0, 1, 2, 3), **kw)
        tok, lab = self.host_batches
        losses, grad = [], None
        for s in range(CHECKED_STEPS):
            k = s % tok.shape[0]
            loss, g = lg(p, jnp.asarray(tok[k]), jnp.asarray(lab[k]), prec)
            lr = learning_rate(o)
            lr = float(lr(s + 1)) if callable(lr) else lr
            p, m, v, g = upd(p, m, v, g, s + 1, _Opt(o, lr=lr))
            losses.append(float(loss))
            if s == 0:
                grad = norms(g)
            del g
        del m, v
        delta = norms(jax.tree.map(jnp.subtract, p, init()))
        return {"losses": losses, "grad": grad, "delta": delta}


def run(ctx) -> dict:
    """One run of a split training cell; see ``bench.run`` for ``ctx``.
    With the ``control`` hook the float8 reference's readings are compared
    in place of the program's."""
    from repro.tracing import Tracer

    tracer = Tracer() if ctx.trace else None
    cell = SplitTrainCell(ctx.cfg, ctx.mix, ctx.seed, ctx.spans, ctx.hooks,
                          tracer)
    ctx.mark("weights and optimizer state")
    t = time.perf_counter()
    prog = cell.first_steps()
    ctx.mark("first steps")
    log(f"first {CHECKED_STEPS} steps (compile included) "
        f"{time.perf_counter() - t:.3f} s; losses {prog['losses']}")
    nbytes = cell.compiled_bytes()
    log(f"train step: {nbytes} B by memory_analysis (per chip)")
    ctx.start_window()
    w = cell.window(ctx.seconds, ctx.tick)
    ctx.end_window()
    if tracer is not None:
        tracer.close()
    peak = cell.free_program_state()
    ref = cell.reference()
    if ctx.hooks.get("control"):
        prog = cell.reference("fp8")
    nums = compare(prog, ref)
    rate = w["tokens"] / (w["t1"] - w["t0"])
    steps = np.array([s["t1"] - s["t0"] for s in w["intervals"]])
    log(f"window: {w['steps']} steps, {w['depth']} in flight, "
        f"{w['tokens']} tokens in {w['t1'] - w['t0']:.6f} s; "
        f"between losses s min {steps.min():.6f} median "
        f"{np.median(steps):.6f} max {steps.max():.6f}; reference losses "
        f"{ref['losses']}")
    counts = [e for e in (tracer.events() if tracer else [])
              if e["name"] == "moe.rows"]
    if counts:
        held = np.array([e["value"] for e in counts])
        log(f"moe.rows: {len(counts)} steps, held rows per layer (mean) "
            f"{held.mean(0).round(1).tolist()}, largest held expert's "
            f"{np.max([e['largest'] for e in counts], 0).tolist()}")
    return {
        "attempted": w["steps"], "failed": 0,
        "e2e": {"train_tokens_per_s": rate},
        "checks": sorted(nums.items()),
        "memory": {"peak_bytes_in_use": peak, "program_bytes": nbytes},
        "record": {"steps": w["intervals"], "window": w,
                   "batch": ctx.mix["batch"], "layers": cell.layers,
                   "chips": len(cell.devices),
                   "moe_rows": [{"step": e["step"], "held": e["value"],
                                 "largest": e["largest"]} for e in counts]},
        "ok": True,
    }
