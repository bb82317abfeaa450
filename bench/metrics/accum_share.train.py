"""Split executor: device time under the pipeline.accum scope (the 1F1B
scan's gradient accumulation, the embedding's scatter-add included) over
busy time in the traced window (%).
Read from the run's profiler trace by ``bench.scopes``; nothing where the
program has no named scopes."""
from pathlib import Path

from bench import scopes

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    return scopes.share(scopes.read_run(rec, ROOT), ("pipeline.accum",))
