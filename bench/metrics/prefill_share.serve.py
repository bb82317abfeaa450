"""Serving engine: device time under the engine.prefill scope (the
admission prefill: the model over the padded arrivals, the cache merge
and token 0's sampling) over busy time in the traced window (%).
Read from the run's profiler trace by ``bench.scopes``; nothing where the
program has no named scopes."""
from pathlib import Path

from bench import scopes

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    return scopes.share(scopes.read_run(rec, ROOT), ("engine.prefill",))
