"""Model (KV cache): device time spent writing and moving the KV cache
over busy time in the traced window (%): the model.kv_write scope (every
layer's write of the new positions, in prefill and in each decode step)
plus model.layers' own time, outside the model.block it holds (the layer
scan slicing each layer's cache out of the stack and stacking the new
caches, with the copies XLA puts inside that loop).
Read from the run's profiler trace by ``bench.scopes``; nothing where the
program has no named scopes."""
from pathlib import Path

from bench import scopes

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    return scopes.share(scopes.read_run(rec, ROOT), ("model.kv_write",),
                        own=("model.layers",))
