"""The continuous-batching engine: ONE jitted step, slot-indexed state.

Each call to the engine step (a) admits up to ``A`` newly arrived
requests into free slots - in-trace, via a cumsum pack over the free-slot
mask: arrival row ``i`` lands in the free slot of rank ``i``, (b)
prefills the arrival buffer alone, cut to ``L`` positions, the smallest
of four length classes (``runners.prefill_lengths``: ``P`` / 8, / 4, / 2,
``P``) that holds the tick's longest admitted prompt, with the head on
each row's last prompt position only (``runners.prefill_rows``); each
taken row's cache positions are then written into its slot
(``runners.write_rows``), ``[0, L)`` as computed and ``[L, P)`` as zeros.
``lax.switch`` picks the class and ``lax.cond`` skips the whole sub-step
on ticks with no arrivals. (c) decodes ``decode_chunk`` tokens for every
active slot in one ``lax.scan`` (slot-indexed KV writes, per-slot
positions, active masking, on-device sampling).

All shapes are static - (N) slots, (A, P) arrival buffers, one prefill
branch per length class, fixed chunk - so arrivals, completions, and
re-plans never retrace: the step stays one compiled trace for the whole
service lifetime (``step.trace_count`` audits this, same idiom as
``core.splitting.make_plan_scorer``).

Named scopes (``jax.named_scope``, op metadata only) label the device
time: ``engine.admit``, ``engine.prefill``, ``engine.decode`` and
``engine.sample`` (inside the other two); the model adds
``model.layers`` (its layer loop, which carries the stacked caches),
``model.block`` (the loop's body) and ``model.kv_write`` (the in-place
write of each layer's new K/V rows into the carried stack). The decode
scan carries that same stack from step to step, so with the state
donated a decode step writes one position per slot and layer in place
and never slices out or restacks a layer's cache.

Invariant the bit-identity proof leans on: KV caches only ever hold
FINITE values. Freed slots are not zeroed - their stale rows are masked
out of attention by the per-row causal mask, and a masked FINITE value
is a bitwise no-op on the softmax (exact-zero weight), whereas a NaN/Inf
would poison the row max. An admitted slot's positions [P, cache_len)
keep the previous occupant's values; like every stale row in the new
request's decode region, each is overwritten the tick before it could
first be attended.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.serving.batching import _row_sample
from repro.serving.runners import (prefill_class, prefill_lengths,
                                   prefill_rows, write_rows)

Array = jax.Array


class EngineState(NamedTuple):
    caches: object          # runner cache pytree, slot axis = num_slots
    gen_target: Array       # (N,) int32 tokens wanted per slot
    pos: Array              # (N,) int32 per-slot KV entry count
    last_tok: Array         # (N,) int32 token feeding the next decode
    n_gen: Array            # (N,) int32 tokens generated so far
    active: Array           # (N,) bool slot is mid-request
    req_id: Array           # (N,) int32 request id (-1 = never used)
    gen_buf: Array          # (N, G) int32 generated tokens per slot
    busy_steps: Array       # () int64-ish f32: sum of active slots/decode step
    decode_steps: Array     # () f32: total decode steps run


def init_engine_state(runner, num_slots: int, prompt_pad: int,
                      max_new: int, cache_len: int | None = None
                      ) -> EngineState:
    n, p, g = num_slots, prompt_pad, max_new
    if cache_len is None:
        cache_len = p + g
    state = EngineState(
        caches=runner.init_caches(n, cache_len),
        gen_target=jnp.zeros((n,), jnp.int32),
        pos=jnp.zeros((n,), jnp.int32),
        last_tok=jnp.zeros((n,), jnp.int32),
        n_gen=jnp.zeros((n,), jnp.int32),
        active=jnp.zeros((n,), bool),
        req_id=jnp.full((n,), -1, jnp.int32),
        gen_buf=jnp.zeros((n, g), jnp.int32),
        busy_steps=jnp.zeros((), jnp.float32),
        decode_steps=jnp.zeros((), jnp.float32),
    )
    mesh = getattr(runner, "mesh", None)
    if mesh is not None:
        # match the step's OUTPUT placement from the start (caches are
        # stage-sharded by runner.init_caches, everything else comes out
        # of the stage pass replicated): a sharding flip between the
        # first and second call would compile the engine step twice
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        state = state._replace(**{
            f: jax.device_put(getattr(state, f), rep)
            for f in EngineState._fields if f != "caches"})
    return state


def evict_slots(state: EngineState, mask) -> EngineState:
    """Free the masked slots WITHOUT touching their caches.

    The host calls this between engine ticks when the device carrying a
    stage dies: the in-flight requests are requeued and their slots
    handed back to the admitter. Caches are left stale on purpose - the
    finite-garbage invariant (module docstring) makes masked stale rows
    a bitwise no-op, exactly as after a normal completion, so eviction
    cannot perturb the tokens of requests it never touched. Plain slot
    bookkeeping on fixed shapes: the next engine step reuses the same
    compiled trace.
    """
    mask = jnp.asarray(mask, bool)
    return state._replace(
        active=state.active & ~mask,
        req_id=jnp.where(mask, jnp.int32(-1), state.req_id),
        n_gen=jnp.where(mask, jnp.int32(0), state.n_gen),
    )


def make_engine_step(runner, *, num_slots: int, arrival_slots: int,
                     prompt_pad: int, max_new: int, decode_chunk: int = 8,
                     temperature: float = 0.0, base_key=None,
                     skip_idle_prefill: bool = True):
    """Build the engine step. Returns ``step`` with a ``.trace_count``
    list ([0] on build; each RETRACE appends - the audit test pins
    ``len == 1`` across arrivals/completions/re-plans).

    ``step(params, state, arr_prompt (A, P), arr_plen (A,), arr_gen (A,),
    arr_req (A,), n_arr scalar)`` -> ``(state, report)`` where ``report``
    is the small host readback ``{active, req_id, n_gen, admitted}``.
    Jit with ``jax.jit(step, donate_argnums=(1,))`` so the caches update
    in place.

    ``skip_idle_prefill``: wrap the prefill sub-step in ``lax.cond`` so
    no-arrival ticks skip its FLOPs. Safe for the pipeline runner too:
    the predicate (``take.any()``) is computed from replicated state, so
    every stage shard takes the same branch and the prefill pass's
    collectives rendezvous uniformly. ``False`` runs the (masked)
    prefill unconditionally every tick.
    """
    if base_key is None:
        base_key = jax.random.PRNGKey(0)
    n, a, g = num_slots, arrival_slots, max_new
    trace_count: list = []

    def step(params, state: EngineState, arr_prompt, arr_plen, arr_gen,
             arr_req, n_arr):
        trace_count.append(1)

        # ---- admission: pack arrivals into free slots, in-trace --------
        with jax.named_scope("engine.admit"):
            free = ~state.active
            order = jnp.cumsum(free.astype(jnp.int32)) - 1  # rank among free
            take = free & (order < n_arr)                   # (N,)
            ai = jnp.clip(order, 0, a - 1)                  # arrival row/slot
            rows = jnp.arange(a) < take.sum()               # taken rows
            slot = jnp.zeros((a,), jnp.int32).at[               # row -> slot
                jnp.where(take, order, a)].set(
                    jnp.arange(n, dtype=jnp.int32), mode="drop")
            sel = lambda arr, old: jnp.where(take, arr[ai], old)
            gen_target = sel(arr_gen, state.gen_target)
            req_id = sel(arr_req, state.req_id)
            n_gen = jnp.where(take, 0, state.n_gen)
            gen_buf = jnp.where(take[:, None], 0, state.gen_buf)
            active = state.active | take

        # ---- prefill sub-step: the arrival rows at a length class ------
        # (each class returns its scratch padded to one shape, and the
        # rows are written after the switch: a switch whose branches each
        # updated the carried caches would copy them)
        def at_length(length):
            return lambda: prefill_rows(
                runner, params, state.caches, arr_prompt[:, :length],
                arr_plen, pad_to=prompt_pad)

        def do_prefill(operand):
            caches, last_tok, pos = operand
            longest = jnp.max(jnp.where(rows, arr_plen, 1))
            logits, fresh = jax.lax.switch(
                prefill_class(prompt_pad, longest),
                [at_length(l) for l in prefill_lengths(prompt_pad)])
            caches = write_rows(caches, fresh, slot, rows)
            with jax.named_scope("engine.sample"):
                tok0 = _row_sample(logits.astype(jnp.float32), base_key,
                                   arr_req, jnp.zeros((a,), jnp.int32),
                                   temperature)
            last_tok = jnp.where(take, tok0[ai], last_tok)
            pos = jnp.where(take, arr_plen[ai], pos)
            return caches, last_tok, pos

        operand = (state.caches, state.last_tok, state.pos)
        with jax.named_scope("engine.prefill"):
            if skip_idle_prefill:
                caches, last_tok, pos = jax.lax.cond(
                    take.any(), do_prefill, lambda op: op, operand)
            else:
                caches, last_tok, pos = do_prefill(operand)
        gen_buf = jnp.where(take[:, None],
                            gen_buf.at[:, 0].set(last_tok), gen_buf)
        n_gen = jnp.where(take, 1, n_gen)
        # a gen_target==1 request completes at admission
        active = active & (n_gen < jnp.maximum(gen_target, 1))

        # ---- decode chunk: one scan, every slot at its own position ----
        def dstep(carry, _):
            caches, last_tok, pos, n_gen, active, gen_buf, busy = carry
            busy = busy + active.sum().astype(jnp.float32)
            logits, caches = runner.decode(params, last_tok[:, None],
                                           caches, pos)
            with jax.named_scope("engine.sample"):
                nxt = _row_sample(logits.astype(jnp.float32), base_key,
                                  req_id, n_gen, temperature)
            last_tok = jnp.where(active, nxt, last_tok)
            written = jax.vmap(
                lambda row, t, i: jax.lax.dynamic_update_slice(
                    row, t[None], (i,))
            )(gen_buf, last_tok, jnp.clip(n_gen, 0, g - 1))
            gen_buf = jnp.where(active[:, None], written, gen_buf)
            pos = jnp.where(active, pos + 1, pos)
            n_gen = jnp.where(active, n_gen + 1, n_gen)
            active = active & (n_gen < gen_target)
            return (caches, last_tok, pos, n_gen, active, gen_buf, busy), None

        carry = (caches, last_tok, pos, n_gen, active, gen_buf,
                 state.busy_steps)
        with jax.named_scope("engine.decode"):
            (caches, last_tok, pos, n_gen, active, gen_buf, busy), _ = (
                jax.lax.scan(dstep, carry, None, length=decode_chunk))

        state = EngineState(
            caches=caches, gen_target=gen_target,
            pos=pos, last_tok=last_tok, n_gen=n_gen, active=active,
            req_id=req_id, gen_buf=gen_buf, busy_steps=busy,
            decode_steps=state.decode_steps + decode_chunk,
        )
        report = {"active": active, "req_id": req_id, "n_gen": n_gen,
                  "admitted": take}
        return state, report

    step.trace_count = trace_count
    return step
