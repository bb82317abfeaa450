"""Chaos invariants: SIGKILL-resume training, fault-injected serving,
torn checkpoint writes.

The three acceptance criteria of the fault-injection subsystem:

* a training run SIGKILLed mid-chunk and resumed from its checkpoint
  directory finishes with metric trajectories BIT-IDENTICAL to an
  uninterrupted run (``launch.chaos`` harness, exercised in-process via
  its own subprocess machinery);
* serving under a fault schedule completes every request, adds zero
  engine retraces, and requests the outage never touched (and even the
  evicted ones, thanks to rid-keyed sampling) produce tokens bitwise
  identical to the fault-free run;
* a SIGKILL landing mid-``save_pytree`` can never leave a torn archive
  where a resumable checkpoint is expected (atomic temp + rename).
"""
import json
import os

import numpy as np
import pytest

import jax

from repro.checkpoint.store import load_pytree, save_pytree
from repro.checkpoint.train_state import (latest_checkpoint_step,
                                          save_train_checkpoint)


# ---------------------------------------------------------------------------
# SIGKILL mid-chunk + resume == uninterrupted, bit-identical


def test_sigkill_resume_metrics_bit_identical(tmp_path):
    """The full kill-and-resume dance through the ``launch.chaos``
    harness: launch a checkpointed training subprocess, SIGKILL it after
    its first resumable checkpoint, relaunch into the same directory,
    and compare against an uninterrupted reference run element-for-
    element (float equality, no tolerance)."""
    from repro.launch import chaos

    rc = chaos.main([
        "--dir", str(tmp_path), "--seed", "5", "--episodes", "8",
        "--warmup", "4", "--num-envs", "2", "--checkpoint-every", "2",
        "--kill-after", "2", "--timeout", "420",
    ])
    assert rc == 0


def test_chaos_parent_never_imports_jax(tmp_path):
    """On an accelerator a chip belongs to one process: the harness's
    parent must stay off JAX so its training children can hold the chip.
    Everything the parent runs - the module, checkpoint polling, the
    compile-cache rule - imports no JAX."""
    import subprocess
    import sys

    from conftest import REPO, SRC

    code = f"""
import sys
from repro.launch import chaos
from repro.launch.compile_cache import enable_compile_cache
from repro.checkpoint.train_state import latest_checkpoint_step
enable_compile_cache()
assert latest_checkpoint_step({str(tmp_path)!r}) is None
assert "jax" not in sys.modules, sorted(m for m in sys.modules if "jax" in m)
print("JAX_FREE")
"""
    env = dict(os.environ, PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "JAX_FREE" in out.stdout


# ---------------------------------------------------------------------------
# fault-injected serving: untouched requests bitwise, zero retraces


def _serve_pair():
    from repro.core import faults as F
    from repro.serving import ServeConfig, ServingService, poisson_trace

    cfg = ServeConfig(num_slots=3, arrival_slots=2, prompt_pad=8, max_new=8,
                      decode_chunk=2, fault_tick_s=0.02, max_retries=2,
                      retry_backoff_s=0.005)
    svc_free = ServingService(cfg)
    trace = poisson_trace(n_requests=7, rate_per_sec=50.0,
                          vocab_size=svc_free.model_cfg.vocab_size,
                          plen_range=(2, 8), gen_range=(2, 8), seed=3)
    free = svc_free.run(list(trace))
    svc_faulted = ServingService(cfg)
    sched = F.reference_schedule(1, 1, tick_seconds=cfg.fault_tick_s)
    faulted = svc_faulted.run(list(trace), faults=sched)
    return trace, free, faulted, svc_free, svc_faulted


def test_serving_fault_injection_invariants():
    trace, free, faulted, svc_free, svc_faulted = _serve_pair()
    # every request completes despite the outage
    assert faulted["num_requests"] == len(trace) == free["num_requests"]
    # the outage actually fired and was recovered from
    assert faulted["fault_events"] >= 1
    assert faulted["recovery_ticks"] >= 1
    assert faulted["retries"] >= 1
    # zero retraces: injection, eviction, and recovery all ran through
    # the single compiled engine trace
    assert svc_faulted.step.trace_count == [1]
    assert svc_free.step.trace_count == [1]
    # fault-free runs report zeroed failure accounting
    assert free["fault_events"] == 0 and free["evictions"] == 0
    assert free["recovery_ticks"] == 0 and free["expired"] == []
    # completions bitwise identical to the fault-free run - for EVERY
    # request: untouched ones by slot-content independence, evicted ones
    # because per-(rid, token) sampling keys make the regenerated stream
    # identical to the lost one
    for r in trace:
        assert np.array_equal(free["completions"][r.rid],
                              faulted["completions"][r.rid]), r.rid


def test_serving_deadline_expiry():
    """A request whose deadline passes while it waits in the queue is
    dropped and reported, not admitted."""
    from repro.serving import Request, ServeConfig, ServingService

    cfg = ServeConfig(num_slots=2, arrival_slots=2, prompt_pad=8, max_new=4,
                      decode_chunk=2)
    svc = ServingService(cfg)
    v = svc.model_cfg.vocab_size
    rng = np.random.default_rng(0)
    mk = lambda rid, t, dl: Request(
        rid=rid, prompt=rng.integers(0, v, 4).astype(np.int32),
        gen_target=3, arrival_time=t, deadline=dl)
    # rid 1's deadline is BEFORE its arrival: it must expire untouched
    trace = [mk(0, 0.0, float("inf")), mk(1, 0.05, 0.01)]
    res = svc.run(trace)
    assert res["expired"] == [1]
    assert sorted(res["completions"]) == [0]


def test_serving_empty_trace_and_zero_pop():
    from repro.serving import RequestQueue, ServeConfig, ServingService

    q = RequestQueue([])
    assert q.pop(0) == [] and q.pop(-3) == [] and q.peek(5) == []
    assert q.exhausted
    svc = ServingService(ServeConfig(num_slots=2, arrival_slots=1,
                                     prompt_pad=8, max_new=4,
                                     decode_chunk=2))
    res = svc.run([])
    assert res["num_requests"] == 0 and res["ticks"] == 0
    # percentiles are 0.0, not NaN (JSON gates choke on NaN)
    assert res["p50_latency_s"] == 0.0 and res["p99_latency_s"] == 0.0


# ---------------------------------------------------------------------------
# torn-write regression: atomic save_pytree


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnraisableExceptionWarning")
def test_save_pytree_is_atomic_under_interrupt(tmp_path):
    """Simulate a SIGKILL mid-save: interrupt the write at every byte
    boundary the implementation flushes through, and the destination
    must either hold the OLD complete archive or not exist - never a
    torn half-archive."""
    tree = {"a": np.arange(100, dtype=np.float32),
            "b": np.ones((32, 32), np.float32)}
    path = os.path.join(tmp_path, "ck.npz")
    save_pytree(tree, path)
    good = open(path, "rb").read()

    # a crash BEFORE the rename leaves the old archive intact: emulate by
    # failing the temp write partway
    import repro.checkpoint.store as store

    class Boom(RuntimeError):
        pass

    real_open = open
    calls = {"n": 0}

    class TornFile:
        """Write-limited file wrapper: the Nth flush dies mid-archive."""

        def __init__(self, f):
            self._f = f

        def write(self, data):
            calls["n"] += 1
            if calls["n"] > 1:
                raise Boom()
            return self._f.write(data)

        def __getattr__(self, name):
            return getattr(self._f, name)

        def __enter__(self):
            self._f.__enter__()
            return self

        def __exit__(self, *a):
            return self._f.__exit__(*a)

    def exploding_open(p, mode="r", *a, **kw):
        f = real_open(p, mode, *a, **kw)
        if str(p).endswith(".tmp") and "w" in mode:
            return TornFile(f)
        return f

    tree2 = {"a": np.zeros(100, dtype=np.float32),
             "b": np.zeros((32, 32), np.float32)}
    store.open = exploding_open  # shadows the builtin inside the module
    try:
        with pytest.raises(Boom):
            save_pytree(tree2, path)
    finally:
        del store.open
    # old archive untouched, temp file cleaned up
    assert open(path, "rb").read() == good
    assert not os.path.exists(path + ".tmp")
    restored = load_pytree(path, tree)
    assert np.array_equal(np.asarray(restored["a"]), tree["a"])


def test_garbage_latest_and_orphan_json_fall_back(tmp_path):
    """A crash between the (atomic) npz write and the json write leaves
    an orphan half; a torn LATEST write leaves garbage. Neither may be
    offered for resume - the scan falls back to the newest COMPLETE
    step instead of crashing or resuming a half-checkpoint."""
    d = str(tmp_path)
    state = {"w": np.arange(8, dtype=np.float32)}
    save_train_checkpoint(d, 2, state, {"ep": 2, "meta": {}})
    save_train_checkpoint(d, 4, state, {"ep": 4, "meta": {}})
    # orphan step 6: json without its npz (the npz write never landed,
    # atomicity guarantees no partial file), plus a garbage LATEST
    with open(os.path.join(d, "step_00000006.json"), "w") as f:
        json.dump({"step": 6, "ep": 6, "meta": {}}, f)
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("not-a-step")
    assert latest_checkpoint_step(d) == 4


def test_save_train_checkpoint_npz_is_atomic(tmp_path):
    """The train-state writer inherits store atomicity: after any
    completed save, the npz under the step path is a loadable archive
    (np.load validates the zip directory)."""
    d = str(tmp_path)
    state = {"w": np.arange(8, dtype=np.float32),
             "k": jax.random.PRNGKey(0)}
    save_train_checkpoint(d, 1, state, {"ep": 1, "meta": {}})
    p = os.path.join(d, "step_00000001.npz")
    with np.load(p, allow_pickle=False) as z:
        assert "__manifest__" in z
    assert latest_checkpoint_step(d) == 1
