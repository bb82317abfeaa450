"""Serving path: ``ServingService.run(trace, realtime=True)`` as an open
loop, each engine tick timed and annotated as ``bench.tick``.

Set-up builds the service on the benchmark's weights, compiles the
engine step once and runs one admitting and one non-admitting tick on a
throw-away state. The window serves every request due in
``[0, seconds)`` and drains them. From the tick spans alone each request
gets its first-token tick (the tick that admitted it: prefill gives token
0), its last-token tick (the tick after which its slot is free) and the
tokens each tick delivered to it (``token_gaps``).

Correctness: once the window has closed and the program's state is
freed, a sample of finished requests drawn from the seed, the longest
among them and one from every slot, is run through the float32 reference
(``bench.reference``) over prompt + served tokens. The compared number is
the widest gap by which a served token's reference logit lies below the
reference's best.
"""
from __future__ import annotations

import time

import numpy as np

from bench import gen
from bench.common import Spans, log, peak_in_use, program_bytes, \
    program_model_config, quantile
from bench.reference import Reference
from bench.weights import flatten, make_params


class ServeCell:
    def __init__(self, cfg: dict, mix: dict, seed: int, spans: Spans,
                 hooks: dict | None = None):
        import jax
        import jax.numpy as jnp

        from repro.serving import ServeConfig, ServingService
        from repro.serving.engine import init_engine_state

        self.cfg, self.mix, self.spans = cfg, mix, spans
        self.hooks = hooks or {}
        e = mix["engine"]
        self.layers = cfg["num_hidden_layers"]
        self.dtype = jnp.dtype(cfg["serve_dtype"])
        self.sc = ServeConfig(
            arch=cfg["module"], reduced=False,
            num_layers=self.layers, num_slots=e["num_slots"],
            arrival_slots=e["arrival_slots"], prompt_pad=e["prompt_pad"],
            max_new=e["max_new"], decode_chunk=e["decode_chunk"],
            temperature=0.0, compute_dtype=cfg["serve_dtype"])
        program_model_config(cfg, self.layers)
        self.reseed(seed)
        self.svc = ServingService(self.sc, params=self.params)
        if "engine_step" in self.hooks:
            self.svc._jstep = jax.jit(self.hooks["engine_step"](self.svc.step),
                                      donate_argnums=(1,))
        self._fresh = lambda: init_engine_state(
            self.svc.runner, e["num_slots"], e["prompt_pad"], e["max_new"])

    def reseed(self, seed: int) -> None:
        """New weights (and, once the service exists, a fresh state) from
        ``seed``; the old ones are dropped first, so two never coexist."""
        svc = getattr(self, "svc", None)
        if svc is not None:
            svc.params = svc.state = None
        self.seed, self.params = seed, None
        self.params = make_params(self.cfg, self.layers, seed, self.dtype)
        if svc is not None:
            svc.params = self.params
            svc.state = self._fresh()

    # -- set-up -------------------------------------------------------------
    def _arrivals(self, n: int):
        import jax.numpy as jnp

        a, p = self.sc.arrival_slots, self.sc.prompt_pad
        ap = np.zeros((a, p), np.int32)
        ap[:n, :4] = 1
        al = np.full((a,), 4, np.int32)
        ag = np.full((a,), 2, np.int32)
        ar = np.where(np.arange(a) < n, np.arange(a), -1).astype(np.int32)
        return (jnp.asarray(ap), jnp.asarray(al), jnp.asarray(ag),
                jnp.asarray(ar), jnp.int32(n))

    def warm(self) -> dict:
        """Compile the engine step (one trace serves both kinds of tick) and
        run one admitting and one non-admitting tick."""
        import jax

        svc = self.svc
        t = time.perf_counter()
        compiled = svc._jstep.lower(svc.params, svc.state,
                                    *self._arrivals(1)).compile()
        out = {"program_bytes": program_bytes(compiled),
               "compile_s": time.perf_counter() - t}
        for n in (1, 0):
            svc.state, rep = svc._jstep(svc.params, svc.state,
                                        *self._arrivals(n))
            jax.block_until_ready(rep)
        svc.state = self._fresh()
        out["traces"] = len(svc.step.trace_count)
        return out

    # -- window -------------------------------------------------------------
    def window(self, seconds: float, tick=lambda: None) -> dict:
        """Serve every request due in ``[0, seconds)``; ``tick`` is called
        before each engine tick."""
        import jax

        from repro.serving import Request

        vocab = self.cfg["vocab_size"]
        reqs = gen.requests(self.mix, self.seed, seconds, vocab)
        self.reqs = {r.rid: r for r in reqs}
        trace = [Request(rid=r.rid, prompt=r.prompt, gen_target=r.out_len,
                         arrival_time=r.due) for r in reqs]
        svc, spans = self.svc, self.spans
        inner = svc._jstep
        ticks = []
        slot_gen = np.zeros(self.sc.num_slots, np.int64)
        self.slot_of = {}

        def timed(params, state, ap, al, ag, ar, n_arr):
            tick()
            with spans.span("bench.tick") as a:
                state, rep = inner(params, state, ap, al, ag, ar, n_arr)
                rep = jax.device_get(rep)
            adm = rep["admitted"]
            rid, ngen, act = rep["req_id"], rep["n_gen"], rep["active"]
            for i in np.nonzero(adm)[0]:
                self.slot_of[int(rid[i])] = int(i)
            # decode steps each slot took in this tick: from its count
            # after admission (1) or after the last tick, to its count now
            before = np.where(adm, 1, slot_gen)
            steps = np.where(rid >= 0, ngen - before, 0)
            a.update(admitted=[int(r) for r in rid[adm]],
                     done=[int(r) for r, on in zip(rid, act)
                           if r >= 0 and not on],
                     slots=[(int(r), int(b), int(s)) for r, b, s in
                            zip(rid, before, steps) if r >= 0 and s > 0],
                     active_after=int(act.sum()))
            slot_gen[:] = ngen
            ticks.append(a)
            return state, rep

        svc._jstep = timed
        t0 = time.perf_counter()
        try:
            res = svc.run(trace, realtime=True)
        finally:
            svc._jstep = inner
        self.res = res
        return self._summarize(ticks, t0, reqs, res)

    def _summarize(self, ticks, t0, reqs, res) -> dict:
        first, last = {}, {}
        for k in ticks:
            for r in k["admitted"]:
                first.setdefault(r, k)
            for r in k["done"]:
                last.setdefault(r, k)
        ttft, tpot_req, one_tok = [], [], 0
        for r in reqs:
            if r.rid not in first or r.rid not in last:
                continue
            ttft.append(first[r.rid]["t1"] - (t0 + r.due))
            n = len(res["completions"].get(r.rid, ()))
            if n > 1:
                tpot_req.append(
                    (last[r.rid]["t1"] - first[r.rid]["t1"]) / (n - 1))
            else:
                one_tok += 1
        tpot, counts = token_gaps(ticks)
        miscounted = sum(counts.get(rid, 0) != len(t) - 1
                         for rid, t in res["completions"].items() if len(t))
        # how late the generator ran: for requests due while no tick ran,
        # the wait from the due time to the start of the next tick
        starts = np.array([k["t0"] for k in ticks])
        ends = np.array([k["t1"] for k in ticks])
        lag = 0.0
        for r in reqs:
            due = t0 + r.due
            i = np.searchsorted(starts, due)
            if i < len(starts) and (i == 0 or ends[i - 1] <= due):
                lag = max(lag, starts[i] - due)
        served = {rid: len(t) for rid, t in res["completions"].items()}
        cap_p = sum(r.prompt.size >= self.mix["prompt_len"]["max"]
                    for r in reqs)
        cap_o = sum(r.out_len >= self.mix["output_len"]["max"] for r in reqs)
        return {
            "attempted": len(reqs), "completed": len(served),
            "failed": len(reqs) - len(served),
            "ttft_s": ttft, "tpot_s": tpot, "tpot_req_s": tpot_req,
            "one_token": one_tok, "miscounted": miscounted,
            "generator_lag_s": lag, "ticks": len(ticks),
            "window_t0": t0, "window_t1": ends[-1] if len(ends) else t0,
            "capped_prompt_share": cap_p / len(reqs),
            "capped_output_share": cap_o / len(reqs),
            "traces": len(self.svc.step.trace_count),
            "tokens_served": int(sum(served.values())),
            "plen": {r.rid: int(r.prompt.size) for r in reqs},
        }

    def free_program_state(self) -> int:
        """Read the peak, then drop the engine state (the KV cache)."""
        import jax

        peak = peak_in_use(jax.devices()[:1])
        self.svc.state = None
        return peak

    # -- correctness -----------------------------------------------------------
    def sample(self) -> list[int]:
        """Finished requests drawn from the seed: the longest served, one
        served on each other slot (so every slot's cache rows are read),
        then others in a seeded order until ``check.tokens`` served
        tokens."""
        done = {rid: t for rid, t in self.res["completions"].items()
                if len(t) > 0}
        if not done:
            return []
        longest = max(done, key=lambda r: (len(done[r]), -r))
        rng = np.random.default_rng(self.seed + 1)
        by_slot = {}
        for r in sorted(done):
            by_slot.setdefault(self.slot_of[r], []).append(r)
        pick = [longest] + [int(rng.choice(by_slot[s])) for s in
                            sorted(by_slot) if s != self.slot_of[longest]]
        total = sum(len(done[r]) for r in pick)
        rest = [r for r in rng.permutation(sorted(done)) if r not in pick]
        for r in rest:
            if total >= self.mix["check"]["tokens"]:
                break
            pick.append(int(r))
            total += len(done[r])
        return pick

    def check(self, control: bool = False) -> dict:
        """Widest gap of a served token below the reference's best logit
        (and, with ``control``, the same for the fp8 control's tokens)."""
        import jax
        import jax.numpy as jnp

        ref = Reference(self.cfg)
        flat = flatten(self.params)
        fn = jax.jit(ref.served_gaps, static_argnums=(4,))
        e = self.mix["engine"]
        t_len, k_len, blk = e["prompt_pad"] + e["max_new"], e["max_new"], \
            self.mix["check"]["rows"]
        rows = self.sample()
        gaps, cgaps, n_tok = [], [], 0
        for i in range(0, len(rows), blk):
            part = rows[i:i + blk]
            tok = np.zeros((blk, t_len), np.int32)
            at = np.zeros((blk, k_len), np.int32)
            gold = np.zeros((blk, k_len), np.int32)
            mask = np.zeros((blk, k_len), bool)
            for j, rid in enumerate(part):
                p = self.reqs[rid].prompt
                s = np.asarray(self.res["completions"][rid], np.int32)
                seq = np.concatenate([p, s[:-1]])
                tok[j, :seq.size] = seq
                at[j, :s.size] = p.size - 1 + np.arange(s.size)
                gold[j, :s.size] = s
                mask[j, :s.size] = True
                n_tok += s.size
            g, c = fn(flat, jnp.asarray(tok), jnp.asarray(at),
                      jnp.asarray(gold), control)
            g, c = np.asarray(g), np.asarray(c)
            gaps.append(np.where(mask, g, -np.inf).max())
            cgaps.append(np.where(mask, c, -np.inf).max())
        out = {"served_gap": float(max(gaps)) if gaps else float("inf"),
               "checked_requests": len(rows), "checked_tokens": n_tok}
        if control:
            out["control_gap"] = float(max(cgaps))
        return out


def token_gaps(ticks) -> tuple[list[float], dict]:
    """Every served token after a request's first, as the time per token of
    the tick that delivered it: (that tick's end - the end of the tick that
    last delivered to the request) / tokens delivered. Tokens that come
    with the first one (the admitting tick's decode chunk) count 0. A
    request's mean of these is its time per output token. Returns the
    samples and the count per request."""
    gaps, count, prev = [], {}, {}
    for k in ticks:
        for r in k["admitted"]:
            prev[r] = k["t1"]
        for r, _, steps in k["slots"]:
            g = 0.0 if r in k["admitted"] else (k["t1"] - prev[r]) / steps
            gaps += [g] * steps
            count[r] = count.get(r, 0) + steps
            prev[r] = k["t1"]
    return gaps, count


def run(ctx) -> dict:
    """One run of a serving cell; see ``bench.run`` for ``ctx``. With the
    ``control`` hook the compared number is the float8 control's, put in
    the program's place."""
    control = bool(ctx.hooks.get("control"))
    cell = ServeCell(ctx.cfg, ctx.mix, ctx.seed, ctx.spans, ctx.hooks)
    ctx.mark("weights and service")
    warm = cell.warm()
    ctx.mark("engine step compiled and warmed")
    log(f"engine step: {warm['program_bytes']} B by memory_analysis, "
        f"compile/load {warm['compile_s']:.3f} s, traces {warm['traces']}")
    ctx.start_window()
    w = cell.window(ctx.seconds, ctx.tick)
    ctx.end_window()
    peak = cell.free_program_state()
    chk = cell.check(control=control)
    e2e = {"ttft_p95_ms": 1e3 * _q(w["ttft_s"], 0.95),
           "tpot_p95_ms": 1e3 * _q(w["tpot_s"], 0.95)}
    log(f"requests due {w['attempted']}, completed {w['completed']}, "
        f"failed {w['failed']}; ttft samples {len(w['ttft_s'])}, tpot "
        f"samples {len(w['tpot_s'])} tokens of {len(w['tpot_req_s'])} "
        f"requests ({w['one_token']} one-token requests, "
        f"{w['miscounted']} miscounted); generator lag "
        f"{w['generator_lag_s']:.6f} s; ticks {w['ticks']}; engine traces "
        f"{w['traces']}")
    log(f"ttft p50 {1e3 * _q(w['ttft_s'], 0.5):.3f} ms; tpot by token p50 "
        f"{1e3 * _q(w['tpot_s'], 0.5):.3f} p90 "
        f"{1e3 * _q(w['tpot_s'], 0.9):.3f} ms; tpot by request p50 "
        f"{1e3 * _q(w['tpot_req_s'], 0.5):.3f} p95 "
        f"{1e3 * _q(w['tpot_req_s'], 0.95):.3f} ms; capped prompts "
        f"{w['capped_prompt_share']:.4f}, capped outputs "
        f"{w['capped_output_share']:.4f}; tokens served {w['tokens_served']}")
    log(f"checked {chk['checked_requests']} requests on "
        f"{len({cell.slot_of[r] for r in cell.sample()})} slots, "
        f"{chk['checked_tokens']} served tokens against the reference")
    gap = chk["control_gap"] if control else chk["served_gap"]
    return {
        "attempted": w["attempted"], "failed": w["failed"], "e2e": e2e,
        "checks": [("served_gap", gap)],
        "memory": {"peak_bytes_in_use": peak,
                   "program_bytes": warm["program_bytes"]},
        "record": {"ticks": ctx.spans.of("bench.tick"), "window": w},
        # nothing may compile inside the window
        "ok": w["traces"] == warm["traces"],
    }


def _q(xs, q):
    return quantile(xs, q) if xs else float("nan")
