"""Split executor (optimizer): device time under the optim.clip,
optim.adamw and optim.apply scopes (gradient clipping, AdamW's moments
and update, applying it; no operation lies under two of them) over busy
time in the traced window (%).
Read from the run's profiler trace by ``bench.scopes``; nothing where the
program has no named scopes."""
from pathlib import Path

from bench import scopes

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    return scopes.share(scopes.read_run(rec, ROOT),
                        ("optim.clip", "optim.adamw", "optim.apply"))
