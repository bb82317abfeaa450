"""record_baseline write-once semantics (benchmarks/common.py).

The throughput baseline is an append-only ledger: a benchmark may
backfill NEW metric keys but must never silently clobber a recorded
number - refreshing requires the explicit --force / force=True or the
BENCH_THROUGHPUT_REFRESH=1 escape hatch.
"""
import json
import sys

import pytest

sys.path.insert(0, ".")  # benchmarks/ is a top-level package dir
from benchmarks import common  # noqa: E402


@pytest.fixture()
def baseline(tmp_path, monkeypatch):
    path = tmp_path / "BENCH_throughput.json"
    monkeypatch.setattr(common, "BASELINE_PATH", str(path))
    monkeypatch.delenv("BENCH_THROUGHPUT_REFRESH", raising=False)
    return path


def _read(path):
    with open(path) as f:
        return json.load(f)


def test_first_write_and_refusal(baseline, capsys):
    written = common.record_baseline({"a": 1.0, "b": {"x": 2}})
    assert sorted(written) == ["a", "b"]
    assert _read(baseline) == {"a": 1.0, "b": {"x": 2}}

    # second write: existing keys refused, file untouched, new key merged
    written = common.record_baseline({"a": 99.0, "c": 3.0})
    assert written == ["c"]
    assert _read(baseline)["a"] == 1.0
    assert _read(baseline)["c"] == 3.0
    err = capsys.readouterr().err
    assert "refusing to overwrite" in err and "'a'" in err


def test_force_overwrites_only_callers_keys(baseline):
    common.record_baseline({"a": 1.0, "other": 7.0})
    written = common.record_baseline({"a": 42.0}, force=True)
    assert written == ["a"]
    data = _read(baseline)
    assert data["a"] == 42.0
    assert data["other"] == 7.0  # untouched entries preserved


def test_refresh_env_var(baseline, monkeypatch):
    common.record_baseline({"a": 1.0})
    monkeypatch.setenv("BENCH_THROUGHPUT_REFRESH", "1")
    assert common.record_baseline({"a": 5.0}) == ["a"]
    assert _read(baseline)["a"] == 5.0


def test_noop_returns_empty(baseline):
    common.record_baseline({"a": 1.0})
    assert common.record_baseline({"a": 2.0}) == []
    assert _read(baseline) == {"a": 1.0}


# ---------------------------------------------------------------------------
# run.py --only selection semantics


def test_only_selection_exact_or_prefix():
    """``--only`` matches exact names or explicit ``name_`` prefixes -
    ``fig1`` must NOT silently swallow ``fig10_leakage_attack``, and an
    entry matching nothing is an error, not an empty run."""
    from benchmarks.run import ALL, select

    assert select(ALL, "fig10") == ["fig10_leakage_attack"]
    assert select(ALL, "pipeline") == ["pipeline"]
    assert select(ALL, "zoo_plan_scoring,serving") == [
        "serving", "zoo_plan_scoring"]
    # list-order output regardless of spec order; duplicates collapse
    assert select(ALL, "serving,pipeline,serving") == ["pipeline", "serving"]
    with pytest.raises(SystemExit):
        select(ALL, "fig1")  # prefix of fig10_... but not an explicit one
    with pytest.raises(SystemExit):
        select(ALL, "nope")
    assert "serving" in ALL and "zoo_plan_scoring" in ALL
