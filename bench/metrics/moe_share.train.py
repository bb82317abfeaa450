"""Model (expert layer): device time under the model.moe scope (routing,
dispatch, the grouped matmul and the combine, forward and backward, the
grouped-matmul kernels XLA renames included: ``bench.scope_time``) over
busy time in the traced window (%). Nothing where the program has no
such scope."""
from pathlib import Path

from bench import scope_time, scopes

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    red = scopes.read_run(rec, ROOT)
    moe_s = scope_time.under(rec, ROOT, "model.moe")
    if not moe_s or not red["busy_s"]:
        return None
    return 100.0 * moe_s / red["busy_s"]
