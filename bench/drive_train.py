"""Training path: the 1F1B split executor (``pipeline_step_fn``) followed by
AdamW, one jitted step with donated state; a step ends when its loss is on
the host. In the window, steps are dispatched ``ahead_s`` seconds of
device time ahead of the loss the host waits for (``TrainCell.window``).

Set-up builds one object, the compiled step with its state, and drives it
from the seed through its first three steps on batches whose rows all
differ; the window then runs the same object on. Those three steps give
the readings that the reference (``bench.reference``, float32, plain
AdamW) is compared with once the window has closed:

* ``loss_gap``: the largest relative gap of the three steps' losses;
* ``grad_gap``: over leaves, the gap between the norms of the first
  clipped gradient (the program's is its AdamW first moment after one
  step over ``1 - b1``) and the reference's, over the larger of that
  leaf's reference norm and the median leaf's;
* ``delta_gap``: the same for the parameters' change after three steps,
  over leaves whose reference gradient is at least a thousandth of the
  median leaf's (smaller ones move by round-off alone under Adam).
"""
from __future__ import annotations

import math
import time
from collections import deque

import numpy as np

from bench import gen
from bench.common import Spans, log, peak_in_use, program_bytes, \
    program_model_config
from bench.reference import Reference, adamw_step
from bench.weights import flatten, make_params

CHECKED_STEPS = 3


def _norms_fn(flat):
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in flat.items()}


def norms(flat: dict) -> dict:
    """Per-leaf L2 norms of a flat parameter dict, on the host."""
    import jax

    return {k: float(v) for k, v in jax.jit(_norms_fn)(flat).items()}


def gaps(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf: |norm_prog - norm_ref| over max(norm_ref, median)."""
    keys = sorted(ref) if keep is None else sorted(keep)
    med = float(np.median([ref[k] for k in sorted(ref)]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in keys)


class TrainCell:
    def __init__(self, cfg: dict, mix: dict, seed: int, spans: Spans,
                 hooks: dict | None = None):
        import jax

        from repro.api import PipelineConfig, adamw, make_stage_mesh, \
            pipeline_step_fn
        from repro.optim.optimizers import apply_updates

        self.cfg, self.mix, self.spans = cfg, mix, spans
        self.layers = cfg["train_layers"]
        mc = program_model_config(cfg, self.layers)
        b, o = mix["batch"], mix["optimizer"]
        mesh = make_stage_mesh(1)
        step_fn = pipeline_step_fn(mc, mesh, (self.layers,),
                                   b["microbatches"], pipe=PipelineConfig())
        self.opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"],
                         max_grad_norm=o["max_grad_norm"])

        def train_step(params, opt_state, tokens, labels):
            loss, grads = step_fn(params, tokens, labels)
            ups, opt_state = self.opt.update(grads, opt_state, params)
            return apply_updates(params, ups), opt_state, loss

        if hooks and "train_step" in hooks:
            train_step = hooks["train_step"](train_step)
        self.step = jax.jit(train_step, donate_argnums=(0, 1))
        self.i = 0
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        import jax
        import jax.numpy as jnp

        self.seed, self.i = seed, 0
        self.params = self.opt_state = None
        self.params = make_params(self.cfg, self.layers, seed, jnp.float32)
        self.opt_state = jax.jit(self.opt.init)(self.params)
        tok, lab = gen.batches(self.mix, seed, self.cfg["vocab_size"])
        self.host_batches = (tok, lab)
        self.batches = [(jnp.asarray(t), jnp.asarray(l))
                        for t, l in zip(tok, lab)]

    def send(self):
        """Dispatch the next step; returns its loss, not yet on the host."""
        tokens, labels = self.batches[self.i % len(self.batches)]
        self.params, self.opt_state, loss = self.step(
            self.params, self.opt_state, tokens, labels)
        self.i += 1
        return loss

    def one_step(self) -> float:
        with self.spans.span("bench.step", index=self.i):
            return float(self.send())

    def first_steps(self) -> dict:
        """Steps 1..3 through the window's own call; the readings the
        reference is compared with."""
        b1 = self.mix["optimizer"]["b1"]
        losses = [self.one_step()]
        grad = {k: v / (1 - b1) for k, v in
                norms(flatten(self.opt_state.mu)).items()}
        losses += [self.one_step() for _ in range(CHECKED_STEPS - 1)]
        import jax
        import jax.numpy as jnp

        p0 = make_params(self.cfg, self.layers, self.seed, jnp.float32)
        delta = norms(flatten(jax.tree.map(jnp.subtract, self.params, p0)))
        del p0
        return {"losses": losses, "grad": grad, "delta": delta}

    def compiled_bytes(self) -> int:
        return program_bytes(self.step.lower(
            self.params, self.opt_state, *self.batches[0]).compile())

    def depth(self) -> int:
        """Steps in flight: ``ahead_s`` of the mix over the quickest of
        the checked steps after the first (which compiles or loads)."""
        step_s = min(s["t1"] - s["t0"]
                     for s in self.spans.of("bench.step")[1:CHECKED_STEPS])
        return max(1, math.ceil(self.mix["ahead_s"] / step_s))

    def window(self, seconds: float, tick=lambda: None) -> dict:
        """Steps sent ``depth`` ahead of the one whose loss the host waits
        for, so that the chip stays fed through a host stall shorter than
        ``ahead_s``. Once ``seconds`` are up nothing more is sent, every
        sent step's loss is waited for, and the clock is read after that:
        all those steps count, over all that time. ``intervals`` runs from
        one loss on the host to the next."""
        rows, seq = self.mix["batch"]["rows"], self.mix["batch"]["seq"]
        depth = self.depth()
        pending, done = deque(), []

        def wait():
            i, loss = pending.popleft()
            with self.spans.span("bench.wait", index=i):
                float(loss)
            done.append(time.perf_counter())

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
        import jax

        peak = peak_in_use(jax.devices()[:1])
        self.params = self.opt_state = self.batches = None
        return peak

    # -- reference ------------------------------------------------------------
    def reference(self, prec: str = "f32") -> dict:
        """The first three steps of the plain reference from the same seed,
        weights and batches: losses, first clipped gradient norms, change
        norms."""
        import jax
        import jax.numpy as jnp

        ref = Reference(self.cfg)
        o = self.mix["optimizer"]
        def init():
            return flatten(make_params(self.cfg, self.layers, self.seed,
                                       jnp.float32))

        p = init()
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        lg = jax.jit(ref.loss_and_grad, static_argnums=(3,))
        upd = jax.jit(adamw_step, static_argnums=(5,),
                      donate_argnums=(0, 1, 2, 3))
        tok, lab = self.host_batches
        losses, grad = [], None
        for s in range(CHECKED_STEPS):
            k = s % tok.shape[0]
            loss, g = lg(p, jnp.asarray(tok[k]), jnp.asarray(lab[k]), prec)
            p, m, v, g = upd(p, m, v, g, s + 1, _Opt(o))
            losses.append(float(loss))
            if s == 0:
                grad = norms(g)
            del g
        del m, v
        delta = norms(jax.tree.map(jnp.subtract, p, init()))
        return {"losses": losses, "grad": grad, "delta": delta}


class _Opt(dict):
    """Hashable optimizer settings for a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def compare(prog: dict, ref: dict) -> dict:
    med = float(np.median([ref["grad"][k] for k in sorted(ref["grad"])]))
    moved = [k for k in ref["grad"] if ref["grad"][k] >= 1e-3 * med]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_gap": gaps(prog["grad"], ref["grad"]),
        "delta_gap": gaps(prog["delta"], ref["delta"], keep=moved),
    }


def run(ctx) -> dict:
    """One run of the training cell; see ``bench.run`` for ``ctx``. With
    the ``control`` hook the float8 reference's readings are compared in
    place of the program's."""
    cell = TrainCell(ctx.cfg, ctx.mix, ctx.seed, ctx.spans, ctx.hooks)
    ctx.mark("weights and optimizer state")
    t = time.perf_counter()
    prog = cell.first_steps()
    ctx.mark("first steps")
    log(f"first {CHECKED_STEPS} steps (compile included) "
        f"{time.perf_counter() - t:.3f} s; losses {prog['losses']}")
    nbytes = cell.compiled_bytes()
    log(f"train step: {nbytes} B by memory_analysis")
    ctx.start_window()
    w = cell.window(ctx.seconds, ctx.tick)
    ctx.end_window()
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
        f"{np.median(steps):.6f} p90 {np.quantile(steps, 0.9):.6f} max "
        f"{steps.max():.6f}, {int((steps > 1.5 * np.median(steps)).sum())} "
        f"over 1.5x the median; reference losses {ref['losses']}")
    return {
        "attempted": w["steps"], "failed": 0,
        "e2e": {"train_tokens_per_s": rate},
        "checks": sorted(nums.items()),
        "memory": {"peak_bytes_in_use": peak, "program_bytes": nbytes},
        "record": {"steps": w["intervals"], "window": w, "batch": ctx.mix["batch"]},
        "ok": True,
    }
