"""Serving engine: bit-identity, slot reuse, trace audits, re-planning.

The contract under test (measured on the CPU backend, leaned on by the
engine design):

* per-ROW float results at a FIXED batch shape are bitwise invariant to
  the other rows' contents and to which row a request occupies (but not
  across shapes: a prefill cut to 4 positions is not bitwise the first
  rows of one cut to 8);
* therefore the single-request reference (``generate_reference``: the
  request alone, prefilled in an arrival buffer at the length class of
  the tick that admitted it, decoded in a batch padded to the engine's
  slot count) must match the engine's output for that request BITWISE,
  no matter when it arrived, which slot it landed in, or what stale
  garbage the slot's KV ring held;
* the engine step stays ONE compiled trace across arrivals, completions,
  idle ticks, and re-plans (all shapes static).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.model import init_params
from repro.serving import (
    ServeConfig, ServingService, SlotScheduler, RequestQueue, Request,
    SingleDeviceRunner, generate_reference, generate_static,
    decode_python_loop, poisson_trace,
)
from repro.serving.engine import init_engine_state, make_engine_step
from repro.serving.runners import check_servable, prefill_lengths
from repro.tracing import Tracer


def _model(num_layers=2):
    cfg = ServeConfig(num_layers=num_layers).model_config()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _trace(cfg, n=6, seed=3, rate=50.0, plen=(2, 8), gen=(2, 8)):
    return poisson_trace(n_requests=n, rate_per_sec=rate,
                         vocab_size=cfg.vocab_size, plen_range=plen,
                         gen_range=gen, seed=seed)


# ---------------------------------------------------------------------------
# vector cache_index: the per-slot decode primitive


def test_vector_cache_index_bitwise_matches_scalar():
    """Decoding B rows at a COMMON position through the vector-(B,)
    cache_index path must be bitwise the scalar-index path (the vector
    path only generalizes the mask/position arithmetic)."""
    cfg, params = _model()
    runner = SingleDeviceRunner(cfg)
    b, p = 3, 6
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, p)), jnp.int32)
    caches = runner.init_caches(b, p + 4)
    _, caches = runner.prefill(params, caches, prompts)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)

    lg_s, c_s, _ = M.forward(params, tok, cfg, caches=caches,
                             cache_index=jnp.asarray(p, jnp.int32),
                             compute_dtype=jnp.float32)
    lg_v, c_v, _ = M.forward(params, tok, cfg, caches=caches,
                             cache_index=jnp.full((b,), p, jnp.int32),
                             compute_dtype=jnp.float32)
    assert jnp.array_equal(lg_s, lg_v)
    for a, bb in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        assert jnp.array_equal(a, bb)


def test_check_servable_moe_and_ssm_gates():
    """Servability matrix: SSM/hybrid stay rejected (padded prefill
    pollutes recurrent state), MoE serves (dropless dispatch computes every
    routed token, padding rows included)."""
    from repro.configs import get_config

    with pytest.raises(ValueError, match="SSM/hybrid"):
        check_servable(get_config("jamba-v0.1-52b").reduced())
    with pytest.raises(ValueError, match="SSM/hybrid"):
        check_servable(get_config("mamba2-370m").reduced())
    check_servable(get_config("qwen3-moe-30b-a3b").reduced())  # servable


# ---------------------------------------------------------------------------
# fused decode scan vs the v0 per-token loop


def test_fused_generate_matches_python_loop():
    cfg, params = _model()
    runner = SingleDeviceRunner(cfg)
    rng = np.random.default_rng(1)
    b, p, g = 4, 6, 8
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, p)), jnp.int32)
    plens = jnp.asarray([6, 3, 5, 2], jnp.int32)
    prompts = prompts * (jnp.arange(p)[None, :] < plens[:, None])
    gens = jnp.asarray([8, 2, 5, 1], jnp.int32)

    fused, n_f = generate_static(runner, params, prompts, plens, gens,
                                 max_new=g)
    loop, n_l = decode_python_loop(runner, params, prompts, plens, gens,
                                   max_new=g)
    assert jnp.array_equal(n_f, n_l)
    assert jnp.array_equal(fused, loop)


# ---------------------------------------------------------------------------
# engine vs single-request reference, bitwise


# prompt lengths 1-16 at prompt_pad 16 (classes 2/4/8/16), in groups that
# arrive together, far enough apart that each group drains first: a group
# of several is admitted 2 a tick at the class of the pair's longest
_GROUPS = ((1,), (3, 2), (5, 8, 1), (16, 9), (12, 4, 15, 6, 7, 11, 10,
                                             13, 14))


def _grouped_trace(vocab, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for g, lens in enumerate(_GROUPS):
        for pl in lens:
            out.append(Request(
                rid=len(out),
                prompt=rng.integers(0, vocab, pl).astype(np.int32),
                gen_target=int(rng.integers(2, 9)),
                arrival_time=1000.0 * (g + 1)))
    return out


def _prefill_lens(events, arrival_slots):
    """rid -> the prefill length of the tick that admitted it, from the
    tracer: the serve.prefill_tokens reading sits in that tick's
    serve.admit span, and the tick records the rids it packed."""
    parent = {e["id"]: e.get("parent") for e in events}
    packed = {e["id"]: e["packed"] for e in events
              if e["name"] == "serve.tick" and "packed" in e}
    return {rid: e["value"] // arrival_slots for e in events
            if e["name"] == "serve.prefill_tokens"
            for rid in packed[parent[e["parent"]]]}


def _check_engine_vs_reference(temperature):
    cfg = ServeConfig(num_slots=3, arrival_slots=2, prompt_pad=16,
                      max_new=8, decode_chunk=2, temperature=temperature)
    tr = Tracer()
    svc = ServingService(cfg, tracer=tr)
    # requests through 3 slots: arrivals land mid-flight of earlier
    # requests, every slot is reused, and ticks admit at every class
    trace = _grouped_trace(svc.model_cfg.vocab_size)
    res = svc.run(trace)
    tr.close()
    assert res["num_requests"] == len(trace)
    lens = _prefill_lens(tr.events(), cfg.arrival_slots)
    assert set(lens.values()) == set(prefill_lengths(cfg.prompt_pad))
    for r in trace:
        ref = generate_reference(
            svc.runner, svc.params, r.prompt, gen_target=r.gen_target,
            max_new=cfg.max_new, prompt_pad=cfg.prompt_pad,
            slots=cfg.num_slots, arrival_slots=cfg.arrival_slots,
            prefill_len=lens[r.rid], temperature=temperature,
            base_key=svc.base_key, req_id=r.rid)
        got = res["completions"][r.rid]
        assert np.array_equal(got, np.asarray(ref)), (
            f"request {r.rid}: engine {got} != reference {np.asarray(ref)}")
    # the whole service ran on one compiled engine trace
    assert len(svc.step.trace_count) == 1


def test_engine_bitwise_matches_reference_greedy():
    _check_engine_vs_reference(0.0)


def test_engine_bitwise_matches_reference_sampled():
    """Temperature sampling: per-(request, token) keys are slot- and
    tick-independent, so the engine consumes the same stream as the
    reference."""
    _check_engine_vs_reference(0.7)


def test_slot_reuse_survives_poisoned_stale_cache():
    """Freed slots are NOT zeroed; correctness rests on stale FINITE
    values being masked into exact-zero attention weights. Poison every
    KV ring with large finite garbage between requests and the next
    request must still match the reference bitwise."""
    cfg, params = _model()
    runner = SingleDeviceRunner(cfg)
    n, p, g = 2, 6, 6
    key = jax.random.PRNGKey(0)
    step = make_engine_step(runner, num_slots=n, arrival_slots=1,
                            prompt_pad=p, max_new=g, decode_chunk=3,
                            base_key=key)
    jstep = jax.jit(step)
    state = init_engine_state(runner, n, p, g)
    rng = np.random.default_rng(5)

    def admit_and_drain(state, rid, prompt, gen):
        ap = np.zeros((1, p), np.int32)
        ap[0, :len(prompt)] = prompt
        args = (jnp.asarray(ap), jnp.asarray([len(prompt)], jnp.int32),
                jnp.asarray([gen], jnp.int32), jnp.asarray([rid], jnp.int32))
        state, rep = jstep(params, state, *args, jnp.int32(1))
        while bool(np.asarray(rep["active"]).any()):
            state, rep = jstep(params, state, *(jnp.zeros_like(a) for a in args),
                               jnp.int32(0))
        slot = int(np.asarray(rep["req_id"]).tolist().index(rid))
        ngen = int(np.asarray(rep["n_gen"])[slot])
        return state, np.asarray(state.gen_buf)[slot, :ngen]

    pr_a = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    state, _ = admit_and_drain(state, 0, pr_a, 4)

    # poison EVERY slot's KV ring with large finite garbage
    state = state._replace(caches=jax.tree.map(
        lambda c: jnp.full_like(c, 1e4), state.caches))

    pr_b = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    state, got = admit_and_drain(state, 1, pr_b, 5)
    ref = generate_reference(runner, params, pr_b, gen_target=5, max_new=g,
                             prompt_pad=p, slots=n, arrival_slots=1,
                             base_key=key, req_id=1)
    assert np.array_equal(got, np.asarray(ref))
    assert len(step.trace_count) == 1


def test_admitting_tick_leaves_untaken_slots_unchanged():
    """The prefill writes only the slots it takes, and of those only the
    first ``prompt_pad`` positions (the tick's L computed, zeros past
    them): every other slot's cache rows, ``last_tok`` and ``pos`` come
    out bitwise as they went in, and so do a taken slot's positions past
    ``prompt_pad``. ``decode_chunk`` 0 isolates the admission tick; one
    arrival in two arrival rows makes the untaken row write back."""
    cfg, params = _model()
    runner = SingleDeviceRunner(cfg)
    n, a, p, g = 4, 2, 8, 6
    step = jax.jit(make_engine_step(runner, num_slots=n, arrival_slots=a,
                                    prompt_pad=p, max_new=g,
                                    decode_chunk=0))
    rng = np.random.default_rng(7)

    def arrivals(lens, rid0):
        ap = np.zeros((a, p), np.int32)
        for i, pl in enumerate(lens):
            ap[i, :pl] = rng.integers(0, cfg.vocab_size, pl)
        al = np.ones((a,), np.int32)
        al[:len(lens)] = lens
        ar = np.full((a,), -1, np.int32)
        ar[:len(lens)] = rid0 + np.arange(len(lens))
        return (jnp.asarray(ap), jnp.asarray(al),
                jnp.full((a,), g, jnp.int32), jnp.asarray(ar),
                jnp.int32(len(lens)))

    state = init_engine_state(runner, n, p, g)
    state, _ = step(params, state, *arrivals([5, 3], 0))  # slots 0 and 1
    # distinct finite garbage everywhere, so any stray write shows
    state = state._replace(caches=jax.tree.map(
        lambda c: jax.random.normal(jax.random.PRNGKey(1), c.shape, c.dtype),
        state.caches))
    before = jax.tree.map(np.asarray, state)
    state, rep = step(params, state, *arrivals([3], 2))   # class 4, slot 2
    after = jax.tree.map(np.asarray, state)

    assert np.asarray(rep["admitted"]).tolist() == [False, False, True, False]
    untaken = [0, 1, 3]
    for f in ("last_tok", "pos"):
        assert np.array_equal(getattr(after, f)[untaken],
                              getattr(before, f)[untaken]), f
    assert after.pos[2] == 3
    for old, new in zip(jax.tree.leaves(before.caches),
                        jax.tree.leaves(after.caches)):
        assert np.array_equal(new[:, untaken], old[:, untaken])
        assert np.array_equal(new[:, 2, p:], old[:, 2, p:])
        assert not (new[:, 2, 4:p] != 0).any()
        assert (new[:, 2, :3] != old[:, 2, :3]).all()


def test_forward_last_rows_match_full_forward():
    """``M.forward(..., last=)`` returns the full forward's logits at
    those positions, (B, V), and the same caches."""
    cfg, params = _model()
    b, p = 3, 8
    rng = np.random.default_rng(2)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, p)), jnp.int32)
    last = jnp.asarray([7, 0, 4], jnp.int32)
    caches = M.init_caches(cfg, b, p + 4, dtype=jnp.float32)
    kw = dict(caches=caches, cache_index=jnp.zeros((), jnp.int32),
              compute_dtype=jnp.float32)
    full, c_full, _ = M.forward(params, prompts, cfg, **kw)
    rows, c_rows, _ = M.forward(params, prompts, cfg, last=last, **kw)
    assert rows.shape == (b, cfg.vocab_size)
    assert jnp.array_equal(rows, full[jnp.arange(b), last])
    for x, y in zip(jax.tree.leaves(c_full), jax.tree.leaves(c_rows)):
        assert jnp.array_equal(x, y)


# ---------------------------------------------------------------------------
# host-side scheduler / queue / trace units


def test_scheduler_packs_bounded_by_free_slots():
    q = RequestQueue([Request(rid=i, prompt=np.arange(3, dtype=np.int32),
                              gen_target=2, arrival_time=0.0)
                      for i in range(5)])
    q.advance(1.0)
    sched = SlotScheduler(arrival_slots=4, prompt_pad=8)
    reqs, ap, al, ag, ar, n_arr = sched.pack(q, free_slots=2)
    assert [r.rid for r in reqs] == [0, 1] and n_arr == 2
    assert ap.shape == (4, 8) and list(ar) == [0, 1, -1, -1]
    reqs, *_, n_arr = sched.pack(q, free_slots=99)  # capped by arrival_slots
    assert [r.rid for r in reqs] == [2, 3, 4] and n_arr == 3
    assert q.exhausted


def test_scheduler_rejects_overlong_prompt():
    q = RequestQueue([Request(rid=0, prompt=np.zeros(9, np.int32),
                              gen_target=1)])
    q.advance(0.0)
    with pytest.raises(ValueError, match="exceeds prompt_pad"):
        SlotScheduler(arrival_slots=1, prompt_pad=8).pack(q, 1)


def test_poisson_trace_shapes_and_config_roundtrip(tmp_path):
    tr = poisson_trace(n_requests=10, rate_per_sec=5.0, vocab_size=64,
                       plen_range=(2, 6), gen_range=(1, 4), seed=0)
    times = [r.arrival_time for r in tr]
    assert times == sorted(times) and times[0] > 0
    assert all(2 <= r.plen <= 6 and 1 <= r.gen_target <= 4 for r in tr)

    path = tmp_path / "serve.json"
    path.write_text('{"num_slots": 16, "boundaries": [1, 2]}')
    cfg = ServeConfig.load(str(path), {"decode_chunk": 2})
    assert (cfg.num_slots, cfg.decode_chunk, cfg.boundaries) == (16, 2, (1, 2))
    with pytest.raises(KeyError):
        ServeConfig.load(None, {"num_slotz": 4})


# ---------------------------------------------------------------------------
# online re-planner


def test_replanner_matches_fresh_scoring_zero_recompile():
    from repro.core.env import MHSLEnv
    from repro.core.profiles import resnet101_profile
    from repro.serving import OnlineReplanner

    env = MHSLEnv(profile=resnet101_profile(batch=1))
    rp = OnlineReplanner(env, bandwidth_sensitivity=0.5, energy_drain=0.0)
    decisions = [rp.replan(load=l) for l in (0.0, 0.4, 0.9)]
    # shifting load shifted the scenario, all through ONE compiled trace
    assert rp.trace_count[0] == 1
    assert all(d["num_plans"] == decisions[0]["num_plans"] for d in decisions)

    # decision must equal a FRESH scoring pass under the same shifted
    # scenario (independent oracle instance)
    fresh = env.make_split_oracle()
    for load, dec in zip((0.0, 0.4, 0.9), decisions):
        out = fresh(rp.dev_pos, rp.devices, rp.p_tx, rp.decoy_power,
                    rp.shifted_scenario(load))
        delay = np.asarray(out["delay"])
        feas = np.asarray(out["feasible"])
        best = int(np.argmin(np.where(feas, delay, np.inf)))
        assert dec["boundaries"] == tuple(
            int(b) for b in np.asarray(out["boundaries"])[best])
        assert dec["delay"] == pytest.approx(delay[best], rel=0, abs=0)

    # heavier load can only tighten the delay-optimal plan's delay
    assert decisions[2]["delay"] >= decisions[0]["delay"]


def test_service_replan_cadence():
    cfg = ServeConfig(num_slots=2, arrival_slots=2, prompt_pad=8, max_new=4,
                      decode_chunk=4, replan_every=1)
    svc = ServingService(cfg)
    from repro.core.env import MHSLEnv
    from repro.core.profiles import resnet101_profile
    from repro.serving import OnlineReplanner

    svc.attach_replanner(OnlineReplanner(
        MHSLEnv(profile=resnet101_profile(batch=1))))
    res = svc.run(_trace(svc.model_cfg, n=3, gen=(1, 4)))
    assert res["num_requests"] == 3
    assert len(res["replans"]) == res["ticks"]
    assert all(len(r["boundaries"]) > 0 for r in res["replans"])
    assert svc.replanner.trace_count[0] == 1


# ---------------------------------------------------------------------------
# pipeline serving (clean subprocess: forced stage devices)


def test_pipeline_engine_matches_single_device(subproc):
    """The split engine (per-stage KV rings, activations on the wire)
    serves bitwise the same tokens as the single-device engine at
    f32 compute / f32 wire."""
    out = subproc(
        """
import numpy as np
from repro.serving import ServeConfig, ServingService, poisson_trace

kw = dict(num_slots=3, arrival_slots=2, prompt_pad=8, max_new=8,
          decode_chunk=2)
single = ServingService(ServeConfig(**kw))
piped = ServingService(ServeConfig(boundaries=(1, 2), **kw))
trace = poisson_trace(n_requests=5, rate_per_sec=50.0,
                      vocab_size=single.model_cfg.vocab_size,
                      plen_range=(2, 8), gen_range=(2, 8), seed=3)
a = single.run(list(trace))
b = piped.run(list(trace))
assert set(a["completions"]) == set(b["completions"])
for rid in a["completions"]:
    assert np.array_equal(a["completions"][rid], b["completions"][rid]), rid
assert len(piped.step.trace_count) == 1
print("PIPE_SERVE_OK", len(a["completions"]))
""",
        n_devices=2)
    assert "PIPE_SERVE_OK 5" in out


def test_pipeline_serve_moe_prefill_bitwise(subproc):
    """MoE stages through the serving token ring (PR 9): dropless
    dispatch makes every row per-row independent, so the padded pipeline
    prefill must be BITWISE the plain forward pass - for the pure-MoE
    period-1 config AND a mixed MoE/dense period-2 stack - and a decode
    tick must produce finite logits."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.core.pipeline import (
    PipelineConfig, make_stage_mesh, pipeline_serve_fns, stage_kv_caches)
from repro.models import model as M
from repro.models.model import init_params

mesh = make_stage_mesh(2)
base = get_config('qwen3-moe-30b-a3b').reduced()
cases = {
    'period1': replace(base, num_layers=2),
    'period2_mixed': replace(base, num_layers=4, d_ff=96,
                             moe=replace(base.moe, moe_every=2)),
}
for name, cfg in cases.items():
    bounds = (1, cfg.num_layers)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prefill, decode = pipeline_serve_fns(cfg, mesh, bounds)
    b, p = 2, 8
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, p)), jnp.int32)
    caches = stage_kv_caches(cfg, bounds, b, p + 4)
    lg, caches = jax.jit(prefill)(params, caches, prompts)
    ref, _, _ = M.forward(params, prompts, cfg, compute_dtype=jnp.float32)
    assert jnp.array_equal(lg, ref), name
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
    dlg, caches = jax.jit(decode)(params, tok, caches,
                                  jnp.full((b,), p, jnp.int32))
    assert bool(jnp.all(jnp.isfinite(dlg))), name
print('MOE_SERVE_OK', len(cases))
""",
        n_devices=2)
    assert "MOE_SERVE_OK 2" in out


def test_pipeline_prefill_last_rows(subproc):
    """The split prefill with ``last`` positions sends only those rows
    through the head on the last stage: the full pass's logits at those
    positions, (B, V), and the same stage caches."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.core.pipeline import (make_stage_mesh, pipeline_serve_fns,
                                 stage_kv_caches)
from repro.models.model import init_params

cfg = replace(get_config('qwen2.5-3b').reduced(), num_layers=2)
bounds = (1, 2)
params = init_params(jax.random.PRNGKey(0), cfg)
prefill, _ = pipeline_serve_fns(cfg, make_stage_mesh(2), bounds)
b, p = 3, 8
prompts = jnp.asarray(np.random.default_rng(0).integers(
    0, cfg.vocab_size, (b, p)), jnp.int32)
last = jnp.asarray([7, 0, 4], jnp.int32)
caches = stage_kv_caches(cfg, bounds, b, p + 4)
full, c_full = jax.jit(prefill)(params, caches, prompts)
rows, c_rows = jax.jit(prefill)(params, caches, prompts, last)
assert rows.shape == (b, cfg.vocab_size)
assert jnp.array_equal(rows, full[jnp.arange(b), last])
for x, y in zip(jax.tree.leaves(c_full), jax.tree.leaves(c_rows)):
    assert jnp.array_equal(x, y)
print('PIPE_LAST_OK')
""",
        n_devices=2)
    assert "PIPE_LAST_OK" in out


def test_decode_scan_does_not_copy_the_cache():
    """Memory guard: the layer loop carries the stacked KV cache and
    writes each token in place, so a jitted scan of decode steps with the
    caches donated needs far less scratch than one copy of the cache
    (slicing each layer out and restacking it took 1.5 copies here).
    float32: the CPU compiler carries a bfloat16 loop state as float32,
    which alone would read two copies whatever the model does."""
    from dataclasses import replace
    from repro.configs import get_config

    cfg = replace(get_config("stablelm-1.6b").reduced(), num_layers=4,
                  d_model=256, num_heads=8, num_kv_heads=8, head_dim=32)
    runner = SingleDeviceRunner(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    slots, cache_len = 8, 256
    caches = runner.init_caches(slots, cache_len)
    cache_bytes = sum(a.nbytes for a in jax.tree.leaves(caches))
    assert cache_bytes == 4 * slots * cache_len * 8 * 32 * 4 * 2

    def run(params, caches, tok, pos):
        def step(carry, _):
            caches, pos = carry
            logits, caches = runner.decode(params, tok, caches, pos)
            return (caches, pos + 1), logits
        (caches, _), logits = jax.lax.scan(step, (caches, pos), None,
                                           length=4)
        return caches, logits

    tok = jnp.zeros((slots, 1), jnp.int32)
    pos = jnp.arange(slots, dtype=jnp.int32) * 16
    mem = jax.jit(run, donate_argnums=(1,)).lower(
        params, caches, tok, pos).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 0.5 * cache_bytes, (
        mem.temp_size_in_bytes, cache_bytes)
