"""The serving loop under the program's tracer: request timestamps, the
spans of each tick, and that tracing changes neither tokens nor traces."""
import numpy as np
import pytest

from repro.serving import ServeConfig, ServingService, poisson_trace
from repro.serving.runners import prefill_lengths
from repro.tracing import Tracer

CFG = ServeConfig(num_layers=2, num_slots=2, arrival_slots=1, prompt_pad=8,
                  max_new=6, decode_chunk=2)
TICK_CHILDREN = ("serve.admit", "serve.dispatch", "serve.readback",
                 "serve.drain")


def _trace():
    return poisson_trace(n_requests=6, rate_per_sec=200.0,
                         vocab_size=CFG.model_config().vocab_size,
                         plen_range=(2, 8), gen_range=(1, 6), seed=5)


@pytest.fixture(scope="module")
def runs():
    plain = ServingService(CFG)
    res_off = plain.run(_trace())
    tr = Tracer()
    traced = ServingService(CFG, params=plain.params, tracer=tr)
    res_on = traced.run(_trace())
    tr.close()
    return plain, res_off, traced, res_on, tr.events()


def test_request_times_are_ordered(runs):
    _, _, _, res, events = runs
    reqs = [e for e in events if e["name"] == "serve.request"]
    assert sorted(e["rid"] for e in reqs) == sorted(res["completions"])
    for e in reqs:
        assert e["arrival"] <= e["admit"] <= e["first_token"] <= e["done"]
        assert e["t1"] - e["t0"] == pytest.approx(e["done"] - e["arrival"])
    waits = sorted(e["admit"] - e["arrival"] for e in reqs)
    ttft = sorted(e["first_token"] - e["arrival"] for e in reqs)
    pick = lambda xs, q: xs[min(int(q * len(xs)), len(xs) - 1)]
    assert res["queue_wait_p95_s"] == pytest.approx(pick(waits, 0.95))
    assert res["ttft_p95_s"] == pytest.approx(pick(ttft, 0.95))
    assert res["ttft_p50_s"] == pytest.approx(pick(ttft, 0.5))
    assert 0.0 <= res["ttft_p50_s"] <= res["ttft_p95_s"]


def test_request_times_share_the_spans_clock(runs):
    """serve.request times are on perf_counter, like the spans: a request's
    first token is read inside the readback of the tick that packed it,
    and it arrives no earlier than the run began."""
    _, _, _, _, events = runs
    ticks = [e for e in events if e["name"] == "serve.tick"]
    readback = {e["parent"]: e for e in events
                if e["name"] == "serve.readback"}
    packed_by = {rid: t for t in ticks for rid in t.get("packed", ())}
    start = min(t["t0"] for t in ticks)
    end = max(t["t1"] for t in ticks)
    for e in events:
        if e["name"] != "serve.request":
            continue
        tick = packed_by[e["rid"]]
        rb = readback[tick["id"]]
        assert rb["t0"] <= e["first_token"] <= rb["t1"]
        assert tick["t0"] <= e["admit"] <= rb["t0"]
        assert start <= e["arrival"] <= e["admit"]
        assert e["done"] <= end
        assert (e["t0"], e["t1"]) == (e["arrival"], e["done"])


def test_each_dispatching_tick_holds_its_children(runs):
    _, _, _, res, events = runs
    ticks = [e for e in events if e["name"] == "serve.tick"]
    dispatched = [t for t in ticks if "packed" in t]
    assert dispatched and res["ticks"] >= len(dispatched)
    by_parent = {}
    for e in events:
        by_parent.setdefault(e.get("parent"), []).append(e)
    for t in dispatched:
        kids = by_parent.get(t["id"], [])
        names = [k["name"] for k in kids]
        for child in TICK_CHILDREN:
            assert child in names, (t, names)
        for k in kids:
            assert t["t0"] <= k["t0"] <= k["t1"] <= t["t1"]
        order = [names.index(c) for c in TICK_CHILDREN[1:]]
        assert order == sorted(order)
        assert set(t) >= {"tick", "pending", "free", "packed", "active_after"}
    # every admitted request is packed by exactly one tick
    packed = [r for t in dispatched for r in t["packed"]]
    assert sorted(packed) == sorted(res["completions"])


def test_tracing_changes_no_token_and_no_trace(runs):
    plain, res_off, traced, res_on, _ = runs
    assert sorted(res_off["completions"]) == sorted(res_on["completions"])
    for rid, toks in res_off["completions"].items():
        np.testing.assert_array_equal(toks, res_on["completions"][rid])
    assert len(plain.step.trace_count) == len(traced.step.trace_count) == 1


def test_untraced_service_keeps_null_tracer(runs):
    plain = runs[0]
    assert not plain.tracer.enabled
    assert plain.tracer.events() == []


def test_admitting_ticks_count_prefill_and_prompt_tokens(runs):
    """Each tick that admits records, in its packing serve.admit span,
    the positions the prefill computes (arrival rows x the smallest class
    holding the longest packed prompt) and the packed prompts' tokens."""
    _, _, _, _, events = runs
    plen = {r.rid: r.plen for r in _trace()}
    parent = {e["id"]: e.get("parent") for e in events}
    ticks = {e["id"]: e for e in events
             if e["name"] == "serve.tick" and e.get("packed")}
    counts = {}
    for e in events:
        if e["name"] in ("serve.prefill_tokens", "serve.prompt_tokens"):
            assert [x["name"] for x in events
                    if x["id"] == e["parent"]] == ["serve.admit"]
            counts.setdefault(parent[e["parent"]], {})[e["name"]] = e["value"]
    assert set(counts) == set(ticks)
    for tid, c in counts.items():
        lens = [plen[r] for r in ticks[tid]["packed"]]
        cls = min(n for n in prefill_lengths(CFG.prompt_pad)
                  if n >= max(lens))
        assert c == {"serve.prefill_tokens": CFG.arrival_slots * cls,
                     "serve.prompt_tokens": sum(lens)}
