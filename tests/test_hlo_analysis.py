"""HLO collective parser unit tests."""
import numpy as np

from repro.launch.hlo_analysis import (
    parse_collectives,
    split_computations,
    loop_multipliers,
)

HLO = """HloModule test, num_partitions=4

%body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %ag = f32[8,8]{1,0} all-gather(%x), channel_id=1, replica_groups=[2,2]<=[4], dimensions={0}
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %ag)
}

%cond.1 (p: (s32[], f32[8,8])) -> pred[] {
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %ar = f32[4,4]{1,0} all-reduce(%a), replica_groups=[1,4]<=[4], to_apply=%add
  %w = (s32[], f32[8,8]) while(%init), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}
"""


def test_split_computations():
    comps = split_computations(HLO)
    assert set(comps) == {"body.1", "cond.1", "main"}


def test_flat_parse():
    st = parse_collectives(HLO)
    # all-gather result 8*8*4 = 256 B, group size 2 -> wire 128
    assert st.result_bytes["all-gather"] == 256
    assert st.wire_bytes["all-gather"] == 128.0
    # all-reduce result 4*4*4 = 64 B, group 4 -> 2*(3/4)*64 = 96
    assert st.wire_bytes["all-reduce"] == 96.0


def test_loop_aware_parse():
    st = parse_collectives(HLO, loop_aware=True)
    assert st.counts["all-gather"] == 5  # trip count from backend_config
    assert st.wire_bytes["all-gather"] == 5 * 128.0
    assert st.counts["all-reduce"] == 1


def test_loop_multipliers_trip_fallback():
    hlo = HLO.replace(', backend_config={"known_trip_count":{"n":"5"}}', "")
    st = parse_collectives(hlo, loop_aware=True)
    assert st.counts["all-gather"] == 5  # constant(5) in the condition


def test_pipeline_collective_counts_synthetic():
    """Per-tick normalization: loop-aware issue counts divided by the
    schedule's tick count."""
    from repro.launch.hlo_analysis import pipeline_collective_counts

    per_tick = pipeline_collective_counts(HLO, n_ticks=5)
    assert per_tick["all-gather"] == 1.0  # 5 loop issues over 5 ticks
    assert per_tick["all-reduce"] == 1 / 5  # entry-level, outside the loop


def test_overlap_issues_no_more_collectives_than_sync(subproc):
    """Regression gate for the double-buffered transport (satellite d):
    compiling the overlapped 1F1B executor must not issue more
    collectives per tick (ppermute hops, psum reductions) than the
    synchronous handoff - overlap only MOVES the hop to the top of the
    tick."""
    out = subproc(
        """
import json
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.models import init_params
from repro.core.pipeline import PipelineConfig, make_stage_mesh, pipeline_step_fn
from repro.launch.hlo_analysis import pipeline_collective_counts

cfg = replace(get_config('qwen2.5-3b').reduced(), num_layers=4)
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_stage_mesh(3)
rng = np.random.default_rng(0)
m = 3
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (m * 2, 16)), jnp.int32)
labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (m * 2, 16)), jnp.int32)
bounds = (1, 3, 4)
ticks = m + 2 * (len(bounds) - 1)
counts = {}
for tr in ('sync', 'overlap'):
    fn = pipeline_step_fn(cfg, mesh, bounds, m,
                          pipe=PipelineConfig(transport=tr, compute_dtype='float32'))
    hlo = jax.jit(fn).lower(params, tokens, labels).compile().as_text()
    counts[tr] = pipeline_collective_counts(hlo, ticks)
assert any('permute' in k for k in counts['sync']), counts['sync']
assert set(counts['overlap']) <= set(counts['sync']), counts
for kind, sync_n in counts['sync'].items():
    assert counts['overlap'].get(kind, 0.0) <= sync_n + 1e-9, (kind, counts)
print('HLO_COUNTS_OK', json.dumps(counts))
""",
        n_devices=3,
    )
    assert "HLO_COUNTS_OK" in out
