"""Scenario parameters as a runtime pytree + scenario-batched train/eval.

The seed froze every physics constant (``NetworkConfig`` fields,
``monitor_prob``, ``power_levels``, budgets, ``leak_scale``) into jit
closures, so each point of the paper's sweeps (Figs. 5/6/8) paid a full
recompile and the scenario axis could not ride the vectorized rollout
engine. This module splits the env configuration into

* **static structure** - shapes only: U devices, E_max eavesdroppers,
  S stages, NBINS size bins, number of power levels. These stay on
  ``MHSLEnv`` and fix every array shape.
* **dynamic physics** - ``ScenarioParams``, a pytree of jnp scalars /
  small vectors passed as a *runtime argument* through
  ``channel -> leakage -> env -> rollout -> trainers``. One compiled
  train/eval step serves every sweep point; sweeping is just calling the
  same compiled function with different leaf values, or vmapping over a
  stacked scenario batch.

Sweep axes that change a SHAPE (more devices, more stages) still require
a new env; eavesdropper count specifically does NOT - pad to ``E_max``
and vary ``eave_mask`` (Fig. 6's sweep runs in one padded env).

Composition with ``num_envs``: the scenario axis vmaps OUTSIDE the env
population, giving ``(num_scenarios, num_envs, T, ...)`` trajectories
from a single jitted call (``make_population_rollout`` /
``make_population_evaluator``), and ``train_population`` trains one agent
per scenario in lockstep on device.
"""
from __future__ import annotations

import dataclasses
import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import NetworkConfig

Array = jax.Array


class ScenarioParams(NamedTuple):
    """Dynamic physics of one MHSL scenario (all leaves are jnp arrays).

    Every field is a runtime value: changing any of them re-uses the
    existing jit cache. Vector fields are sized by the env's static
    shapes (``E_max`` eavesdroppers, ``P`` power levels).
    """

    monitor_prob: Array  # (E,) per-eavesdropper q_e
    eave_mask: Array  # (E,) 1.0 = active, 0.0 = padded-out eavesdropper
    know_eave_locations: Array  # () 1.0 = l_M observed, 0.0 = blinded
    gamma_t: Array  # () per-iteration delay budget (s)
    gamma_e: Array  # () per-iteration energy budget (J)
    bandwidth_hz: Array  # () B
    noise_w: Array  # () N0 * B in watts
    rayleigh_o: Array  # () o
    power_levels: Array  # (P,) discrete transmit powers (W)
    leak_scale: Array  # () leakage reward scale
    area_m: Array  # () deployment area side length
    f_cpu_hz: Array  # () f^B device CPU clock
    theta_chip: Array  # () vartheta chip energy coefficient
    lambda_f: Array  # () Eq. 8 complexity multiplier (seed applied 1.0)
    lambda_b: Array  # () Eq. 9 complexity multiplier (seed applied 1.0)
    # per-hop link model (heterogeneous wireless links between consecutive
    # stages): hop k of a plan transmits at hop_bandwidth_hz[k] (thermal
    # noise scales with it) and pays a fixed hop_latency_s[k] on every
    # activation/cotangent transmission. Defaults (full(bandwidth_hz),
    # zeros) reproduce the uniform-link seed physics bit-exactly.
    hop_bandwidth_hz: Array  # (max_split - 1,)
    hop_latency_s: Array  # (max_split - 1,)
    # architecture-aware state pricing (NetworkConfig.state_cycles_per_bit):
    # maintenance cycles per resident state bit folded into the Eq. 8-9
    # compute terms. 0.0 reproduces homogeneous residual-MLP pricing.
    state_cycles_per_bit: Array  # ()

    @property
    def num_eaves(self) -> int:
        return self.monitor_prob.shape[-1]

    @property
    def num_power_levels(self) -> int:
        return self.power_levels.shape[-1]

    @property
    def num_hops(self) -> int:
        return self.hop_bandwidth_hz.shape[-1]


def scenario_from_net(
    net: NetworkConfig,
    *,
    know_eave_locations: bool = True,
    leak_scale: float = 1.0,
) -> ScenarioParams:
    """Build the dynamic-physics pytree matching a Table-I config.

    ``lambda_f``/``lambda_b`` default to 1.0: the seed env never threaded
    ``NetworkConfig.lambda_f`` into Eqs. 8-9 (faithfulness ledger), and
    this constructor preserves that behaviour exactly. Sweeps can set
    them explicitly via ``scenario_grid``.
    """
    e = net.num_eaves
    # every leaf carries an explicit (strong) dtype: weak-typed python
    # scalars would make `scenario=None` default-path traces incompatible
    # with explicit sweep scenarios and silently retrace the engine
    return ScenarioParams(
        monitor_prob=jnp.full((e,), net.monitor_prob, jnp.float32),
        eave_mask=jnp.ones((e,), jnp.float32),
        know_eave_locations=jnp.asarray(
            1.0 if know_eave_locations else 0.0, jnp.float32),
        gamma_t=jnp.asarray(net.gamma_t, jnp.float32),
        gamma_e=jnp.asarray(net.gamma_e, jnp.float32),
        bandwidth_hz=jnp.asarray(net.bandwidth_hz, jnp.float32),
        noise_w=jnp.asarray(net.noise_w, jnp.float32),
        rayleigh_o=jnp.asarray(net.rayleigh_o, jnp.float32),
        power_levels=jnp.asarray(net.power_levels, jnp.float32),
        leak_scale=jnp.asarray(leak_scale, jnp.float32),
        area_m=jnp.asarray(net.area_m, jnp.float32),
        f_cpu_hz=jnp.asarray(net.f_cpu_hz, jnp.float32),
        theta_chip=jnp.asarray(net.theta_chip, jnp.float32),
        lambda_f=jnp.asarray(1.0, jnp.float32),
        lambda_b=jnp.asarray(1.0, jnp.float32),
        hop_bandwidth_hz=jnp.asarray(net.hop_bandwidth_hz, jnp.float32),
        hop_latency_s=jnp.asarray(net.hop_latency_s, jnp.float32),
        state_cycles_per_bit=jnp.asarray(net.state_cycles_per_bit,
                                         jnp.float32),
    )


# ---------------------------------------------------------------------------
# grid construction + stacking
# ---------------------------------------------------------------------------


def replace_param(base: ScenarioParams, name: str, value) -> ScenarioParams:
    """``_replace`` one field, broadcasting scalars to the field's shape
    (e.g. ``monitor_prob=0.3`` -> ``full((E,), 0.3)``)."""
    ref = getattr(base, name)
    val = jnp.broadcast_to(jnp.asarray(value, ref.dtype), ref.shape)
    return base._replace(**{name: val})


def scale_param(base: ScenarioParams, name: str, scale) -> ScenarioParams:
    """Multiplicative sibling of :func:`replace_param`: scale one field
    elementwise (broadcast against the field's shape), keeping its dtype.
    Degradation sweeps (``core.faults.degrade_scenario``) ride this so a
    faulted scenario stays the same pytree structure as the base one."""
    ref = getattr(base, name)
    val = ref * jnp.asarray(scale, ref.dtype)
    return base._replace(**{name: val.astype(ref.dtype)})


def shift_param(base: ScenarioParams, name: str, delta) -> ScenarioParams:
    """Additive sibling of :func:`replace_param` (see :func:`scale_param`)."""
    ref = getattr(base, name)
    val = ref + jnp.asarray(delta, ref.dtype)
    return base._replace(**{name: val.astype(ref.dtype)})


def with_active_eaves(base: ScenarioParams, count: int) -> ScenarioParams:
    """Scenario with only the first ``count`` eavesdroppers active: their
    mask is 1, the rest are padding (zero monitoring, zero observation)."""
    e = base.num_eaves
    if not 0 <= count <= e:
        raise ValueError(f"count must be in [0, {e}], got {count}")
    mask = (jnp.arange(e) < count).astype(base.eave_mask.dtype)
    return base._replace(eave_mask=mask)


def scenario_grid(base: ScenarioParams, **axes: Sequence) -> List[ScenarioParams]:
    """Cartesian product over named parameter axes.

    ``scenario_grid(base, monitor_prob=[0.3, 0.6], gamma_e=[50.0, 75.0])``
    yields 4 scenarios in row-major order of the keyword arguments. The
    special axis ``active_eaves`` takes integer counts and varies
    ``eave_mask`` (padded-E sweep).
    """
    names = list(axes)
    out = []
    for combo in itertools.product(*(axes[n] for n in names)):
        sp = base
        for name, value in zip(names, combo):
            if name == "active_eaves":
                sp = with_active_eaves(sp, int(value))
            else:
                sp = replace_param(sp, name, value)
        out.append(sp)
    return out


def stack_scenarios(scenarios: Sequence[ScenarioParams]) -> ScenarioParams:
    """Stack N scenarios into one batched pytree (leading axis N) ready
    for the population vmap."""
    if not scenarios:
        raise ValueError("need at least one scenario")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *scenarios)


def num_scenarios(stacked: ScenarioParams) -> int:
    return int(stacked.monitor_prob.shape[0])


def jit_cache_size(fn) -> int:
    """Compiled-variant count of an engine callable (recompile auditing).

    Accepts either a jitted function or one of this module's /
    ``rollout``'s wrappers (which expose their inner jit as ``.jitted``).
    Falls back to the wrapper's retrace counter on jax versions without
    the (private) ``_cache_size`` introspection.
    """
    inner = getattr(fn, "jitted", fn)
    if hasattr(inner, "_cache_size"):
        return inner._cache_size()
    trace_count = getattr(fn, "trace_count", None)
    if trace_count is not None:
        return trace_count[0]
    raise AttributeError(
        "no jit cache introspection available on this jax version")


# ---------------------------------------------------------------------------
# population rollout / evaluation: scenario axis composed with num_envs
# ---------------------------------------------------------------------------


def make_population_rollout(env, policy, hist_len: int, *,
                            share_params: bool = True,
                            extra_record=None):
    """Rollout vmapped over scenarios x envs in one jitted call.

    Returns ``run(params, rkeys, akeys, scenarios)`` where ``rkeys`` /
    ``akeys`` are ``(num_envs,)`` key batches shared across scenarios
    (controlled comparison: every sweep point replays the same episode
    draws), ``scenarios`` is a stacked ``ScenarioParams`` with leading
    axis N, and trajectory leaves come back ``(N, num_envs, T, ...)``.
    ``share_params=False`` maps ``params`` over the scenario axis too
    (one agent per scenario, as produced by ``train_population``).

    The wrapper exposes ``run.jitted`` (for ``jit_cache_size``) and
    ``run.trace_count`` (a 1-element list bumped on every retrace).
    """
    from repro.core.agents import rollout as R

    one = R.make_episode_rollout(env, policy, hist_len,
                                 extra_record=extra_record)
    trace_count = [0]

    def _per_scenario(params, rkeys, akeys, sp):
        trace_count[0] += 1  # executes only while tracing
        st0 = jax.vmap(env.reset, in_axes=(0, None))(rkeys, sp)
        return jax.vmap(one, in_axes=(None, 0, 0, None))(
            params, st0, akeys, sp
        )

    jitted = jax.jit(jax.vmap(
        _per_scenario,
        in_axes=(None if share_params else 0, None, None, 0),
    ))

    def run(params, rkeys, akeys, scenarios):
        return jitted(params, rkeys, akeys, scenarios)

    run.jitted = jitted
    run.trace_count = trace_count
    return run


def make_population_evaluator(env, policy, hist_len: int = 1, *,
                              share_params: bool = True,
                              leakage_model=None):
    """One compiled eval step for a whole scenario sweep.

    Returns ``evaluate(params, rkeys, akeys, scenarios)`` ->
    ``{"reward", "leak", "viol"}``, each ``(N,)``: per-scenario means
    over the episode batch of per-episode sums. A 5-point
    ``monitor_prob`` grid (or any other parameter grid of the same
    shapes) compiles this exactly once.

    ``leakage_model`` overrides the env's :class:`~repro.core.leakage.
    LeakageModel` for this evaluation (e.g. score an analytically
    trained agent under attacker-measured EmpiricalLeakage values).
    """
    from repro.core.agents import rollout as R

    if leakage_model is not None:
        env = dataclasses.replace(env, leakage_model=leakage_model)

    one = R.make_episode_rollout(env, policy, hist_len)
    trace_count = [0]

    def _per_scenario(params, rkeys, akeys, sp):
        trace_count[0] += 1
        st0 = jax.vmap(env.reset, in_axes=(0, None))(rkeys, sp)
        _, traj = jax.vmap(one, in_axes=(None, 0, 0, None))(
            params, st0, akeys, sp
        )
        return {
            "reward": traj["reward"].sum(axis=-1).mean(),
            "leak": traj["leak"].sum(axis=-1).mean(),
            "viol": traj["viol"].sum(axis=-1).mean(),
        }

    jitted = jax.jit(jax.vmap(
        _per_scenario,
        in_axes=(None if share_params else 0, None, None, 0),
    ))

    def evaluate(params, rkeys, akeys, scenarios):
        return jitted(params, rkeys, akeys, scenarios)

    evaluate.jitted = jitted
    evaluate.trace_count = trace_count
    return evaluate


def evaluate_population(env, policy, params, scenarios, *,
                        episodes: int = 20, seed: int = 1000,
                        hist_len: int = 1, share_params: bool = True,
                        leakage_model=None) -> Dict[str, np.ndarray]:
    """Evaluate ``params`` across a stacked scenario batch in ONE jitted
    call (fresh geometry per episode, same episode keys per scenario).

    Key derivation mirrors ``loops.evaluate_sac`` so a batch-of-1 sweep
    reproduces the single-scenario evaluation numbers. ``leakage_model``
    swaps the leakage pricing for this evaluation (analytic default).
    """
    ev = make_population_evaluator(env, policy, hist_len,
                                   share_params=share_params,
                                   leakage_model=leakage_model)
    key = jax.random.PRNGKey(seed)
    k_reset, k_act = jax.random.split(key)
    out = ev(params, jax.random.split(k_reset, episodes),
             jax.random.split(k_act, episodes), scenarios)
    return {k: np.asarray(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# population training: one SAC agent per scenario, trained in lockstep
# ---------------------------------------------------------------------------


@dataclass
class PopulationResult:
    """Per-scenario training curves + the stacked parameter pytree
    (leading axis = scenario)."""

    results: List[Any] = field(default_factory=list)  # List[TrainResult]
    params: Any = None


def _stack_like(tree, n: int):
    """Zero-initialized copy of ``tree`` with a new leading axis n."""
    return jax.tree.map(lambda x: jnp.zeros((n,) + x.shape, x.dtype), tree)


def train_population(env, cfg, scenarios: ScenarioParams, *,
                     episodes: int = 200, seed: int = 0,
                     warmup_episodes: int = 10, num_envs: int = 1,
                     resample_positions: bool = False, mesh=None,
                     checkpoint_dir: Optional[str] = None,
                     checkpoint_every: int = 0,
                     resume: bool = True) -> PopulationResult:
    """Train one ICM-CA SAC agent per scenario, all scenarios in lockstep.

    The whole chunk cycle - vmapped rollout over ``(N, num_envs)``,
    batched replay writes into N stacked device buffers, N fused update
    scans - runs under single jitted calls with the scenario axis mapped
    by ``jax.vmap``; nothing recompiles across scenarios. Chunking,
    warmup rounding, and metric bookkeeping match ``loops.train_sac``
    (every scenario shares the chunk schedule, reset keys, and action
    keys, so sweep points differ only by their physics).

    ``mesh`` (``distribution.mesh.make_population_mesh``) shards the SCENARIO
    axis across devices: per-scenario agent params, optimizer state,
    replay buffers, and the stacked ``ScenarioParams`` all carry their
    leading ``N`` axis on the mesh, while the shared reset/action keys are
    replicated - pure data parallelism over sweep points, with metrics
    all-gathered by the per-chunk ``device_get``. The compiled chunk
    functions are unchanged, so a 1-device mesh is bit-identical to the
    plain vmap path (pinned by ``tests/test_population_mesh.py``).

    ``checkpoint_dir`` / ``checkpoint_every`` / ``resume`` behave as in
    ``loops.train_sac``: complete loop state saved at chunk boundaries,
    bit-exact continuation on restore.
    """
    from repro.checkpoint import train_state as TS
    from repro.core.agents import rollout as R
    from repro.core.agents import sac as SAC
    from repro.core.agents.loops import (
        TrainResult, _reduced_chunk_metrics, _sac_example, _SAC_FIELDS,
    )
    from repro.distribution import population as PD

    if num_envs < 1:
        raise ValueError(f"num_envs must be >= 1, got {num_envs}")
    n = num_scenarios(scenarios)
    adims = env.action_dims
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    params = jax.vmap(
        lambda k: SAC.init_agent(k, env.obs_dim, adims, cfg)
    )(jax.random.split(k0, n))
    update, init_opt = SAC.make_update(adims, cfg)
    opt_state = jax.vmap(init_opt)(params)

    buf = _stack_like(R.buffer_init(cfg.buffer_size, _sac_example(env, cfg)), n)
    n_updates = cfg.updates_per_step * env.episode_len * num_envs
    # the fused train chunk vmapped over the scenario axis: params /
    # optimizer state / buffers / update keys / scenarios are mapped, the
    # shared chunk keys and warmup flag are broadcast. The stacked buffer
    # storage is donated where XLA supports it (in-place ring writes on
    # accelerators; CPU does not implement donation).
    chunk = R.make_train_chunk(
        env, R.uniform_policy(adims), R.sac_policy(adims, cfg), update,
        hist_len=cfg.hist_len, fields=_SAC_FIELDS, batch_size=cfg.batch,
        n_updates=n_updates,
    )
    donate = (2,) if jax.default_backend() != "cpu" else ()
    vm_chunk = jax.jit(
        jax.vmap(chunk.fn, in_axes=(0, 0, 0, None, None, 0, None, 0)),
        donate_argnums=donate,
    )

    pop = PopulationResult(results=[TrainResult() for _ in range(n)])
    seen: List[set] = [set() for _ in range(n)]
    key, reset_key = jax.random.split(key)

    # mesh placement: scenario axis sharded, shared chunk keys replicated
    params = PD.shard_population(params, mesh, n)
    opt_state = PD.shard_population(opt_state, mesh, n)
    buf = PD.shard_population(buf, mesh, n)
    scenarios = PD.shard_population(scenarios, mesh, n)

    # run fingerprint: loop knobs + agent config + the stacked scenario
    # physics; TS.validate_resume hard-errors on any mismatch (editing a
    # sweep grid must not silently resume the old grid's checkpoint)
    meta = dict(seed=seed, num_envs=num_envs, num_scenarios=n,
                warmup_episodes=warmup_episodes,
                resample_positions=resample_positions,
                cfg=repr(cfg), scenario=TS.pytree_fingerprint(scenarios))

    ep = 0
    last_saved = None
    if checkpoint_dir and resume and (
        TS.latest_checkpoint_step(checkpoint_dir) is not None
    ):
        like = dict(params=params, opt_state=opt_state, buf=buf,
                    key=key, reset_key=reset_key)
        _, dev, host = TS.load_train_checkpoint(checkpoint_dir, like)
        TS.validate_resume(host, meta, episodes, checkpoint_dir)
        params, opt_state, buf = dev["params"], dev["opt_state"], dev["buf"]
        key, reset_key = dev["key"], dev["reset_key"]
        ep = last_saved = int(host["ep"])
        for res, saved in zip(pop.results, host["results"]):
            res.episode_reward = list(saved["episode_reward"])
            res.episode_leak = list(saved["episode_leak"])
            res.episode_violation = list(saved["episode_violation"])
            res.states_explored = list(saved["states_explored"])
        seen = [set(s) for s in host["seen"]]

    def _save(ep_now: int) -> None:
        TS.save_train_checkpoint(
            checkpoint_dir, ep_now,
            dict(params=params, opt_state=opt_state, buf=buf,
                 key=key, reset_key=reset_key),
            dict(ep=ep_now, meta=meta,
                 results=[dict(episode_reward=r.episode_reward,
                               episode_leak=r.episode_leak,
                               episode_violation=r.episode_violation,
                               states_explored=r.states_explored)
                          for r in pop.results],
                 seen=[sorted(s) for s in seen]),
        )

    while ep < episodes:
        if (checkpoint_dir and checkpoint_every
                and (last_saved is None or ep - last_saved >= checkpoint_every)):
            _save(ep)
            last_saved = ep
        if resample_positions:
            key, reset_key = jax.random.split(key)
        rkeys = R.episode_reset_keys(reset_key, num_envs, resample_positions)
        key, ksub, ku = jax.random.split(key, 3)
        akeys = jax.random.split(ksub, num_envs)
        rkeys = PD.replicate(rkeys, mesh)
        akeys = PD.replicate(akeys, mesh)
        ukeys = PD.shard_population(jax.random.split(ku, n), mesh, n)

        # every scenario's full chunk cycle in ONE buffer-donated dispatch;
        # the traced warmup flag and per-lane buffer-fill gate replace the
        # host-side `int(buf.size[0])` sync
        train = jnp.asarray(ep >= warmup_episodes)
        params, opt_state, buf, metrics = vm_chunk(
            params, opt_state, buf, rkeys, akeys, ukeys, train, scenarios
        )
        # one device->host transfer of the reduced metrics for all
        # scenarios (all-gathering the scenario shards), then per-episode
        # bookkeeping on each scenario's slice
        host = jax.device_get(metrics)
        for s in range(n):
            _reduced_chunk_metrics(
                pop.results[s], seen[s],
                jax.tree.map(lambda x: x[s], host), ep, episodes, num_envs,
            )
        ep += num_envs

    if checkpoint_dir and last_saved != ep:
        _save(ep)

    pop.params = params
    return pop
