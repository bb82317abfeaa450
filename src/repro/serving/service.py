"""Host-side serving: request queue, slot scheduler, wall-clock loop.

The host does only bookkeeping - every token-level decision lives inside
the jitted engine step. Per tick the host (a) moves requests whose
arrival time has passed into the FIFO queue, (b) packs at most
``min(A, pending, free slots)`` of them into the fixed-shape arrival
buffers, (c) calls the engine step, and (d) drains completions from the
small report readback (pulling ``gen_buf`` rows only for slots that
finished). Idle ticks (nothing pending, nothing active) skip the step
call entirely.

A :class:`repro.tracing.Tracer` passed as ``tracer`` records each loop
turn as a ``serve.tick`` span holding ``serve.admit`` (queue advance,
expiry, packing and the arrival buffers' copies to the device),
``serve.dispatch`` (the engine step's call, which returns once the step is
queued), ``serve.readback`` (the host waits for the report),
``serve.drain`` (completion bookkeeping), ``serve.sleep`` (idle wait) and
``serve.fault`` (outage handling), and one ``serve.request`` event per
completion with its ``rid`` and its ``arrival``, ``admit``,
``first_token`` and ``done`` times. On a tick that admits, ``serve.admit``
also holds two counter readings: ``serve.prefill_tokens``, the positions
the engine's prefill computes (arrival rows x the tick's length class),
and ``serve.prompt_tokens``, the admitted prompts' tokens; their ratio is
the prefill's padding efficiency. Like the spans, these are on
``time.perf_counter``: ``arrival`` is the moment the service clock passed
the request's arrival time (``run`` without ``realtime`` moves that clock
ahead over idle stretches, so it may run ahead of ``perf_counter``).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.serving.config import ServeConfig
from repro.serving.runners import prefill_class, prefill_lengths
from repro.tracing import NULL_TRACER


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (plen,) int32
    gen_target: int
    arrival_time: float = 0.0     # seconds from trace start
    # absolute trace-time completion deadline; inf = none. A request
    # still QUEUED past its deadline is dropped (reported under
    # ``expired``) instead of admitted - under faults an evicted request
    # re-enters the queue and can expire there too.
    deadline: float = float("inf")

    @property
    def plen(self) -> int:
        return int(self.prompt.shape[0])


@dataclass
class Completion:
    rid: int
    tokens: np.ndarray
    arrival_time: float
    admit_time: float
    # end of the report readback of the admitting tick, whose prefill
    # produced token 0
    first_token_time: float
    done_time: float

    @property
    def latency(self) -> float:
        return self.done_time - self.arrival_time

    @property
    def ttft(self) -> float:
        return self.first_token_time - self.arrival_time

    @property
    def queue_wait(self) -> float:
        return self.admit_time - self.arrival_time


class RequestQueue:
    """FIFO of arrived-but-unadmitted requests."""

    def __init__(self, trace: List[Request]):
        self._future = sorted(trace, key=lambda r: r.arrival_time)
        self._ready: deque = deque()

    def advance(self, now: float) -> List[Request]:
        """Move requests whose arrival time has passed into the FIFO;
        returns them."""
        moved = []
        while self._future and self._future[0].arrival_time <= now:
            moved.append(self._future.pop(0))
        self._ready.extend(moved)
        return moved

    def pop(self, k: int) -> List[Request]:
        k = max(min(int(k), len(self._ready)), 0)  # k <= 0 pops nothing
        return [self._ready.popleft() for _ in range(k)]

    def peek(self, k: int) -> List[Request]:
        """First ``k`` ready requests WITHOUT removing them (the
        scheduler validates before it pops, so a rejection never loses
        queued requests)."""
        k = max(min(int(k), len(self._ready)), 0)
        return [self._ready[i] for i in range(k)]

    def requeue_front(self, reqs: List[Request]) -> None:
        """Put evicted in-flight requests back at the HEAD of the queue
        (in the given order) so recovery re-admits them before newer
        arrivals."""
        self._ready.extendleft(reversed(reqs))

    def drop_expired(self, now: float) -> List[Request]:
        """Remove (and return) ready requests past their deadline."""
        expired = [r for r in self._ready if r.deadline <= now]
        if expired:
            dead = {id(r) for r in expired}
            self._ready = deque(r for r in self._ready if id(r) not in dead)
        return expired

    @property
    def pending(self) -> int:
        return len(self._ready)

    @property
    def exhausted(self) -> bool:
        return not self._future and not self._ready

    def next_arrival(self) -> Optional[float]:
        return self._future[0].arrival_time if self._future else None


class SlotScheduler:
    """Packs ready requests into the engine's fixed-shape arrival buffers."""

    def __init__(self, arrival_slots: int, prompt_pad: int):
        self.a = arrival_slots
        self.p = prompt_pad

    def pack(self, queue: RequestQueue, free_slots: int):
        """-> (admitted requests, prompt (A,P), plen, gen, rid, n_arr).

        Rejection is TOTAL: candidates are validated by peek before any
        is popped, so an oversized prompt raises with the queue intact
        (nothing admitted, nothing lost).
        """
        reqs = queue.peek(min(self.a, free_slots))
        for r in reqs:
            if r.plen > self.p:
                raise ValueError(
                    f"request {r.rid} prompt length {r.plen} exceeds "
                    f"prompt_pad {self.p}")
        reqs = queue.pop(len(reqs))
        ap = np.zeros((self.a, self.p), np.int32)
        al = np.ones((self.a,), np.int32)
        ag = np.ones((self.a,), np.int32)
        ar = np.full((self.a,), -1, np.int32)
        for i, r in enumerate(reqs):
            ap[i, :r.plen] = r.prompt
            al[i] = r.plen
            ag[i] = r.gen_target
            ar[i] = r.rid
        return reqs, ap, al, ag, ar, len(reqs)


class ServingService:
    """The continuous-batching service loop over one engine."""

    def __init__(self, cfg: ServeConfig, params=None, mesh=None,
                 tracer=NULL_TRACER):
        import jax
        import jax.numpy as jnp

        from repro.serving.engine import (init_engine_state,
                                          make_engine_step)
        from repro.serving.runners import PipelineRunner, SingleDeviceRunner

        self.cfg = cfg
        self.tracer = tracer
        self.model_cfg = cfg.model_config()
        dtype = jnp.dtype(cfg.compute_dtype)
        if cfg.boundaries is None:
            self.runner = SingleDeviceRunner(self.model_cfg,
                                             compute_dtype=dtype)
        else:
            from repro.core.pipeline import PipelineConfig
            from repro.distribution.mesh import make_stage_mesh

            if mesh is None:
                mesh = make_stage_mesh(len(cfg.boundaries))
            pipe = PipelineConfig(compute_dtype=cfg.compute_dtype,
                                  wire_dtype=cfg.wire_dtype)
            self.runner = PipelineRunner(self.model_cfg, mesh,
                                         cfg.boundaries, pipe=pipe)
        self.params = cfg.init_params() if params is None else params
        self.base_key = jax.random.PRNGKey(cfg.seed)
        self.step = make_engine_step(
            self.runner, num_slots=cfg.num_slots,
            arrival_slots=cfg.arrival_slots, prompt_pad=cfg.prompt_pad,
            max_new=cfg.max_new, decode_chunk=cfg.decode_chunk,
            temperature=cfg.temperature, base_key=self.base_key,
            # safe for BOTH runners: the cond predicate (take.any()) is
            # computed from replicated state, so every stage shard takes
            # the same branch and the prefill pass's collectives
            # rendezvous uniformly (pinned bitwise by the pipeline
            # serving test)
            skip_idle_prefill=True)
        self._jstep = jax.jit(self.step, donate_argnums=(1,))
        self.state = init_engine_state(
            self.runner, cfg.num_slots, cfg.prompt_pad, cfg.max_new)
        self.replanner = None  # attach via attach_replanner()
        # devices the serving pipeline occupies, as FaultSchedule rows:
        # one per stage for split serving, device 0 standalone
        self.stage_devices = (tuple(range(len(cfg.boundaries)))
                              if cfg.boundaries else (0,))

    def attach_replanner(self, replanner) -> None:
        self.replanner = replanner

    def run(self, trace: List[Request], *, realtime: bool = False,
            max_ticks: int = 100_000, faults=None) -> Dict:
        """Serve ``trace`` to completion; returns results + metrics.

        ``realtime=False`` (benchmark mode) treats arrival times as a
        virtual clock that only moves forward when the engine would
        otherwise idle - arrivals still gate admission ORDER, but the
        engine never sleeps, so throughput comparisons are
        compute-bound. ``realtime=True`` sleeps until the next arrival.

        ``faults`` is an optional :class:`repro.core.faults.FaultSchedule`
        covering the service's ``stage_devices``. A tick whose fault-clock
        time (``cfg.fault_tick_s > 0``: deterministic ``tick *
        fault_tick_s``; else the virtual arrival clock) lands inside an
        assigned device's outage window is a FAILED tick: the engine is
        not dispatched, the service retries with bounded exponential
        backoff (``cfg.max_retries`` / ``cfg.retry_backoff_s``), and if
        the device is still down it evicts every in-flight slot
        (``engine.evict_slots``), requeues those requests at the queue
        head, re-plans around the dead devices
        (``replan(exclude_devices=...)``), and jumps the clock to the
        outage's end. Requests the outage never touched complete with
        bitwise-identical tokens to a fault-free run (rid-keyed sampling;
        pinned by ``tests/test_chaos.py``), and injection adds zero
        engine retraces.
        """
        import jax
        import jax.numpy as jnp

        from repro.core import faults as F
        from repro.serving.engine import evict_slots

        trace = list(trace)
        if self.cfg.deadline_s > 0:
            import dataclasses

            trace = [dataclasses.replace(
                r, deadline=min(r.deadline,
                                r.arrival_time + self.cfg.deadline_s))
                for r in trace]
        queue = RequestQueue(trace)
        sched = SlotScheduler(self.cfg.arrival_slots, self.cfg.prompt_pad)
        clock = F.FaultClock(self.cfg.fault_tick_s)
        if faults is not None:
            # host-side numpy mirrors of faults.device_up /
            # faults.next_recovery: the SAME half-open window arithmetic
            # (pinned against the jnp versions by tests/test_chaos.py)
            # without paying a per-tick XLA dispatch + first-call compile
            # inside the timed service loop
            f_start = np.asarray(faults.outage_start, np.float32)
            f_end = np.asarray(faults.outage_end, np.float32)
            f_stage = np.asarray(self.stage_devices, np.int64)

            def _f_up(t):
                t = np.float32(t)
                return ~(((t >= f_start) & (t < f_end)).any(axis=-1))

            def _f_recovery(t):
                t = np.float32(t)
                cov = (t >= f_start[f_stage]) & (t < f_end[f_stage])
                if not cov.any():
                    return float(t)
                return float(max(t, np.where(cov, f_end[f_stage],
                                             -np.inf).max()))
        admit_t: Dict[int, float] = {}
        first_t: Dict[int, float] = {}
        # for the tracer's serve.request events, on perf_counter: the
        # service clock is perf_counter - t0, and t0 moves on idle jumps
        # and fault stalls, so each time takes the t0 of its own moment
        arrive_pc: Dict[int, float] = {}
        admit_t0: Dict[int, float] = {}
        arrive_t = {r.rid: r.arrival_time for r in trace}
        completions: List[Completion] = []
        seen_done = set()
        inflight: Dict[int, Request] = {}
        expired: List[Request] = []
        tr = self.tracer
        t0 = time.perf_counter()
        free = self.cfg.num_slots
        active_rids: set = set()
        replans = []
        fault_events = retries = evictions = recovery_ticks = 0
        tick = 0
        while tick < max_ticks:
            with tr.span("serve.tick", tick=tick) as tick_attrs:
                with tr.span("serve.admit"):
                    now = time.perf_counter() - t0
                    for r in queue.advance(now):
                        arrive_pc[r.rid] = t0 + r.arrival_time
                    expired.extend(queue.drop_expired(now))
                if queue.pending == 0 and not active_rids:
                    if queue.exhausted:
                        break
                    # idle: jump the virtual clock to the next arrival
                    with tr.span("serve.sleep"):
                        nxt = queue.next_arrival()
                        if realtime:
                            time.sleep(max(nxt - now, 0.0))
                        else:
                            t0 -= max(nxt - now, 0.0)
                        for r in queue.advance(time.perf_counter() - t0):
                            arrive_pc[r.rid] = t0 + r.arrival_time
                        expired.extend(
                            queue.drop_expired(time.perf_counter() - t0))
                    if queue.pending == 0 and not active_rids:
                        # early wake / all arrivals expired: nothing to do,
                        # skip the engine dispatch instead of burning a
                        # no-op step (the realtime busy-loop fix)
                        tick += 1
                        continue
                if faults is not None:
                    now = time.perf_counter() - t0
                    t_f = clock.time_of(tick, now)
                    up = _f_up(t_f)
                    down = [d for d in self.stage_devices if not up[d]]
                    if down:
                        with tr.span("serve.fault", down=down):
                            fault_events += 1
                            # bounded exponential backoff before giving up
                            t_probe, backoff = t_f, self.cfg.retry_backoff_s
                            recovered = False
                            for _ in range(max(self.cfg.max_retries, 0)):
                                retries += 1
                                t_probe += backoff
                                backoff *= 2.0
                                probe_up = _f_up(t_probe)
                                if all(probe_up[d]
                                       for d in self.stage_devices):
                                    recovered = True
                                    break
                            if not recovered:
                                # give up on this outage: free every in-flight
                                # slot (the pipeline spans all stage devices),
                                # requeue its requests at the head, and route
                                # re-planning around the dead devices
                                victims = sorted(
                                    (inflight[r] for r in active_rids
                                     if r in inflight),
                                    key=lambda r: (r.arrival_time, r.rid))
                                if victims:
                                    evictions += len(victims)
                                    queue.requeue_front(victims)
                                    self.state = evict_slots(
                                        self.state,
                                        np.asarray(self.state.active))
                                    active_rids = set()
                                    free = self.cfg.num_slots
                                if self.replanner is not None:
                                    occupancy = 0.0
                                    replans.append(self.replanner.replan(
                                        load=occupancy, exclude_devices=down))
                                t_probe = _f_recovery(t_probe)
                            # stall to the recovery point: charge it to the
                            # clock and advance the fault clock past it
                            stall = max(t_probe - t_f, 0.0)
                            if realtime:
                                time.sleep(stall)
                            else:
                                t0 -= stall
                            skipped = clock.ticks_until(t_f, t_probe)
                            recovery_ticks += skipped
                        tick += skipped
                        continue
                if tr.enabled:
                    tick_attrs.update(pending=queue.pending, free=free)
                with tr.span("serve.admit"):
                    reqs, ap, al, ag, ar, n_arr = sched.pack(queue, free)
                    now = time.perf_counter() - t0
                    for r in reqs:
                        admit_t[r.rid] = now
                        admit_t0[r.rid] = t0
                        inflight[r.rid] = r
                    arrivals = (jnp.asarray(ap), jnp.asarray(al),
                                jnp.asarray(ag), jnp.asarray(ar),
                                jnp.int32(n_arr))
                    if tr.enabled and n_arr:
                        # the engine's prefill shape for these arrivals
                        p = self.cfg.prompt_pad
                        cls = prefill_class(p, int(al[:n_arr].max()))
                        tr.count("serve.prefill_tokens",
                                 len(al) * prefill_lengths(p)[cls])
                        tr.count("serve.prompt_tokens",
                                 int(al[:n_arr].sum()))
                with tr.span("serve.dispatch"):
                    self.state, report = self._jstep(
                        self.params, self.state, *arrivals)
                with tr.span("serve.readback"):
                    act = np.asarray(report["active"])
                    rids = np.asarray(report["req_id"])
                    ngen = np.asarray(report["n_gen"])
                    now = time.perf_counter() - t0
                with tr.span("serve.drain"):
                    for r in reqs:  # prefill gave them token 0 this tick
                        first_t[r.rid] = now
                    active_rids = {int(r) for r, a in zip(rids, act)
                                   if a and r >= 0}
                    done_slots = [s for s in range(len(rids))
                                  if rids[s] >= 0 and not act[s]
                                  and int(rids[s]) not in seen_done]
                    if done_slots:
                        # pull only on completions
                        buf = np.asarray(self.state.gen_buf)
                        for s in done_slots:
                            rid = int(rids[s])
                            seen_done.add(rid)
                            inflight.pop(rid, None)
                            c = Completion(
                                rid=rid, tokens=buf[s, :ngen[s]].copy(),
                                arrival_time=arrive_t[rid],
                                admit_time=admit_t[rid],
                                first_token_time=first_t[rid], done_time=now)
                            completions.append(c)
                            if tr.enabled:
                                base = admit_t0[rid]
                                tr.event("serve.request", arrive_pc[rid],
                                         t0 + now, rid=rid,
                                         arrival=arrive_pc[rid],
                                         admit=base + c.admit_time,
                                         first_token=base + first_t[rid],
                                         done=t0 + now)
                    free = int((~act).sum())
                if tr.enabled:
                    tick_attrs.update(packed=[r.rid for r in reqs],
                                      active_after=len(active_rids))
                if (self.replanner is not None and self.cfg.replan_every
                        and tick % self.cfg.replan_every == 0):
                    occupancy = float(act.sum()) / max(len(act), 1)
                    replans.append(self.replanner.replan(load=occupancy))
                tick += 1
        wall = time.perf_counter() - t0
        return self._metrics(completions, wall, tick, replans,
                             expired=expired, fault_events=fault_events,
                             retries=retries, evictions=evictions,
                             recovery_ticks=recovery_ticks)

    def _metrics(self, completions: List[Completion], wall: float,
                 ticks: int, replans, *, expired=(), fault_events: int = 0,
                 retries: int = 0, evictions: int = 0,
                 recovery_ticks: int = 0) -> Dict:
        total_tokens = int(sum(len(c.tokens) for c in completions))
        busy = float(self.state.busy_steps)
        steps = float(self.state.decode_steps)

        def pct(values, q):
            # empty-trace runs report 0.0, not NaN (NaN poisons JSON gates)
            xs = sorted(values)
            return xs[min(int(q * len(xs)), len(xs) - 1)] if xs else 0.0

        lats = [c.latency for c in completions]
        ttft = [c.ttft for c in completions]
        return {
            "completions": {c.rid: c.tokens for c in completions},
            "latencies": {c.rid: c.latency for c in completions},
            "num_requests": len(completions),
            "wall_seconds": wall,
            "ticks": ticks,
            "requests_per_sec": len(completions) / wall if wall else 0.0,
            "tokens_per_sec": total_tokens / wall if wall else 0.0,
            "p50_latency_s": pct(lats, 0.50),
            "p99_latency_s": pct(lats, 0.99),
            # time to first token and the part of it spent queued
            "ttft_p50_s": pct(ttft, 0.50),
            "ttft_p95_s": pct(ttft, 0.95),
            "queue_wait_p95_s": pct((c.queue_wait for c in completions),
                                    0.95),
            # structural accounting (wall-clock independent, as in
            # core.transport): fraction of slot-steps doing useful decode
            "slot_occupancy": busy / (steps * self.cfg.num_slots)
            if steps else 0.0,
            "replans": replans,
            # failure accounting (all zero on fault-free runs)
            "expired": sorted(r.rid for r in expired),
            "fault_events": fault_events,
            "retries": retries,
            "evictions": evictions,
            "recovery_ticks": recovery_ticks,
        }


def poisson_trace(*, n_requests: int, rate_per_sec: float, vocab_size: int,
                  plen_range=(4, 32), gen_range=(4, 24), seed: int = 0
                  ) -> List[Request]:
    """Mixed-length Poisson arrival trace (exponential inter-arrivals)."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for rid in range(n_requests):
        t += float(rng.exponential(1.0 / rate_per_sec))
        pl = int(rng.integers(plen_range[0], plen_range[1] + 1))
        gt = int(rng.integers(gen_range[0], gen_range[1] + 1))
        out.append(Request(
            rid=rid,
            prompt=rng.integers(0, vocab_size, size=pl).astype(np.int32),
            gen_target=gt, arrival_time=t))
    return out
