"""Training loops: episode rollout + off-policy updates (Algorithm 1).

Built on the device-resident rollout engine (``repro.core.agents.rollout``):
``train_sac`` runs each chunk - env reset, the vmapped ``lax.scan``
episode rollout over ``num_envs`` environments, the batched replay-buffer
write, the fused gradient-update scan, and the per-episode metric
reduction - as ONE jitted, buffer-donated call
(``rollout.make_train_chunk``). The only per-chunk host traffic is a
single ``device_get`` of the reduced metrics (episode sums plus packed
discretized-obs state keys); there is no ``int(buf.size)`` sync and no
full-trajectory transfer.

Tracks the paper's figure metrics: accumulated reward per episode (Figs.
3-4), information leaked (Figs. 5-6), and distinct states explored (Fig. 7,
packed key of the discretized observation).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import train_state as TS
from repro.core.agents import action_space as A
from repro.core.agents import rollout as R
from repro.core.agents import sac as SAC
from repro.core.env import MHSLEnv
from repro.distribution import population as PD


def _pack_obs_keys_np(obs: np.ndarray, bins: float = R.OBS_BINS) -> np.ndarray:
    """Vectorized distinct-state keys (paper Fig. 7): discretize every
    observation row with ``round(obs * bins)`` and mix the columns into a
    uint64 key, all in batched numpy - the previous ``_obs_hash`` built a
    Python tuple per row (``num_envs * T`` rows per chunk).

    Bit-compatible with the device-side ``rollout.pack_obs_keys`` lanes
    (``key == (hi << 32) | lo``), and - unlike Python's salted ``hash`` -
    deterministic across interpreter runs, so checkpointed explored-state
    sets resume exactly.
    """
    q = np.round(np.asarray(obs) * bins).astype(np.int32).astype(np.uint32)
    prime = np.uint32(R._KEY_PRIME)
    hi = np.full(q.shape[:-1], R._KEY_BASIS_HI, np.uint32)
    lo = np.full(q.shape[:-1], R._KEY_BASIS_LO, np.uint32)
    for d in range(q.shape[-1]):
        col = q[..., d]
        hi = (hi ^ col) * prime
        lo = (lo ^ col) * prime
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def _combine_key_lanes(packed: np.ndarray) -> np.ndarray:
    """(..., 2) uint32 device key lanes -> (...,) uint64 host keys."""
    p = np.asarray(packed)
    return ((p[..., 0].astype(np.uint64) << np.uint64(32))
            | p[..., 1].astype(np.uint64))


@dataclass
class TrainResult:
    episode_reward: list = field(default_factory=list)
    episode_leak: list = field(default_factory=list)
    episode_violation: list = field(default_factory=list)
    states_explored: list = field(default_factory=list)  # cumulative distinct
    metrics: list = field(default_factory=list)
    # compiles of train_sac's fused chunk over the whole run (1 = no retrace)
    trace_count: int = 0


# transition fields persisted to the SAC replay buffer
_SAC_FIELDS = ("obs", "obs_next", "hist", "hist_mask", "action", "masks",
               "reward", "done")


def _sac_example(env: MHSLEnv, cfg: SAC.SACConfig) -> Dict:
    """Single-transition pytree defining the replay buffer layout."""
    adims = env.action_dims
    pair_dim = env.obs_dim + A.flat_dim(adims)
    return dict(
        obs=jnp.zeros((env.obs_dim,), jnp.float32),
        obs_next=jnp.zeros((env.obs_dim,), jnp.float32),
        hist=jnp.zeros((cfg.hist_len, pair_dim), jnp.float32),
        hist_mask=jnp.zeros((cfg.hist_len,), jnp.float32),
        action={
            "u": jnp.zeros((), jnp.int32),
            "size": jnp.zeros((), jnp.int32),
            "decoys": jnp.zeros((adims["decoys"],), jnp.int32),
            "p_tx": jnp.zeros((), jnp.int32),
            "p_d": jnp.zeros((), jnp.int32),
        },
        masks={
            "u": jnp.zeros((adims["u"],), bool),
            "size": jnp.zeros((adims["size"],), bool),
            "decoys": jnp.zeros((adims["decoys"],), bool),
            "p_tx": jnp.zeros((adims["p_tx"],), bool),
            "p_d": jnp.zeros((adims["p_d"],), bool),
        },
        reward=jnp.zeros((), jnp.float32),
        done=jnp.zeros((), jnp.float32),
    )


def _chunk_metrics(result: TrainResult, seen: set, traj, ep: int,
                   episodes: int, num_envs: int) -> None:
    """Single device->host transfer per chunk; then per-episode bookkeeping
    (reward/leak/violation sums + the distinct-state counter, computed via
    the vectorized numpy packing + ``np.unique`` rather than a Python hash
    loop over every observation row)."""
    host = jax.device_get({
        "obs": traj["obs"],
        "reward": traj["reward"],
        "leak": traj["leak"],
        "viol": traj["viol"],
    })
    keys = _pack_obs_keys_np(host["obs"])  # (num_envs, T)
    for i in range(num_envs):
        if ep + i >= episodes:
            break
        seen.update(int(k) for k in np.unique(keys[i]))
        result.episode_reward.append(float(host["reward"][i].sum()))
        result.episode_leak.append(float(host["leak"][i].sum()))
        result.episode_violation.append(float(host["viol"][i].sum()))
        result.states_explored.append(len(seen))


def _reduced_chunk_metrics(result: TrainResult, seen: set, m, ep: int,
                           episodes: int, num_envs: int) -> None:
    """Bookkeeping from a fused train chunk's device-reduced metrics
    (already on host): per-episode sums are precomputed, observations
    arrive as packed state keys instead of raw rows."""
    keys = _combine_key_lanes(m["obs_keys"])  # (num_envs, T)
    for i in range(num_envs):
        if ep + i >= episodes:
            break
        seen.update(int(k) for k in np.unique(keys[i]))
        result.episode_reward.append(float(m["reward"][i]))
        result.episode_leak.append(float(m["leak"][i]))
        result.episode_violation.append(float(m["viol"][i]))
        result.states_explored.append(len(seen))
    if bool(m["did_update"]):
        result.metrics.append(
            {k: float(v) for k, v in m["update"].items()}
        )


def train_sac(
    env: MHSLEnv,
    cfg: SAC.SACConfig,
    episodes: int = 200,
    seed: int = 0,
    warmup_episodes: int = 10,
    resample_positions: bool = False,
    num_envs: int = 1,
    scenario=None,
    mesh=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    resume: bool = True,
) -> TrainResult:
    """ICM-CA SAC training on the device-resident engine.

    ``scenario`` (a ``repro.core.scenario.ScenarioParams``) overrides the
    env's default physics as a runtime value - training the same env
    object across sweep points re-uses every jit cache. ``None`` keeps
    the constructor defaults. To train a whole scenario batch in one
    vectorized run, use ``repro.core.scenario.train_population``.

    ``num_envs`` environments run as one vmapped population; each chunk -
    the rollout of ``num_envs`` full episodes, the replay write, the
    ``num_envs * episode_len * updates_per_step`` gradient steps (the same
    updates-per-env-step ratio as the seed per-step loop, run with the
    ``cfg.joint_update`` single-backward step by default), and the metric
    reduction - is ONE buffer-donated jitted call
    (``rollout.make_train_chunk``). Note the cadence difference vs the
    seed: updates are
    batched at chunk end with the rollout policy frozen for the episode,
    where the seed interleaved ``updates_per_step`` steps after every env
    step - counts match, training dynamics are the standard batched-RL
    approximation. With ``num_envs > 1`` the warmup boundary rounds UP to
    chunk granularity: a chunk that straddles ``warmup_episodes`` still
    rolls out uniformly and gradient updates start with the first chunk
    that begins at or past the boundary. If ``episodes`` is not a multiple
    of ``num_envs`` the final chunk still trains on the full population
    but only the first ``episodes`` entries are reported.

    ``mesh`` (``distribution.mesh.make_population_mesh``) shards the
    ``num_envs`` axis of env states / key batches and the replay buffer's
    capacity axis across devices; agent params and optimizer state are
    replicated. The
    compiled chunk functions are unchanged - jit propagates the committed
    input shardings - so a 1-device mesh is bit-identical to ``mesh=None``.

    ``checkpoint_dir`` + ``checkpoint_every`` save the complete loop state
    (params, opt state, replay buffer, PRNG keys, episode counter, metric
    curves, explored-state hashes) at chunk boundaries every
    ``checkpoint_every`` episodes, plus once at the end. With ``resume``
    (default) an existing checkpoint in the directory is restored and
    training continues from its episode counter; the resumed trajectory is
    bit-identical to an uninterrupted run.
    """
    if num_envs < 1:
        raise ValueError(f"num_envs must be >= 1, got {num_envs}")
    key = jax.random.PRNGKey(seed)
    adims = env.action_dims
    key, k0 = jax.random.split(key)
    params = SAC.init_agent(k0, env.obs_dim, adims, cfg)
    update, init_opt = SAC.make_update(adims, cfg)
    opt_state = init_opt(params)

    buf = R.buffer_init(cfg.buffer_size, _sac_example(env, cfg))
    n_updates = cfg.updates_per_step * env.episode_len * num_envs
    chunk = R.make_train_chunk(
        env, R.uniform_policy(adims), R.sac_policy(adims, cfg), update,
        hist_len=cfg.hist_len, fields=_SAC_FIELDS, batch_size=cfg.batch,
        n_updates=n_updates,
    )

    result = TrainResult()
    seen: set = set()
    key, kpos = jax.random.split(key)
    reset_key = kpos

    # mesh placement: replicated agent, population-sharded replay storage
    params = PD.replicate(params, mesh)
    opt_state = PD.replicate(opt_state, mesh)
    buf = PD.shard_population(buf, mesh, cfg.buffer_size)

    # run fingerprint saved with every checkpoint: loop knobs plus the
    # agent config and scenario physics the run was trained under -
    # TS.validate_resume hard-errors on any mismatch
    meta = dict(seed=seed, num_envs=num_envs,
                warmup_episodes=warmup_episodes,
                resample_positions=resample_positions,
                cfg=repr(cfg), scenario=TS.pytree_fingerprint(scenario))

    ep = 0
    last_saved = None
    if checkpoint_dir and resume and (
        TS.latest_checkpoint_step(checkpoint_dir) is not None
    ):
        like = dict(params=params, opt_state=opt_state, buf=buf,
                    key=key, reset_key=reset_key)
        step, dev, host = TS.load_train_checkpoint(checkpoint_dir, like)
        TS.validate_resume(host, meta, episodes, checkpoint_dir)
        params, opt_state, buf = dev["params"], dev["opt_state"], dev["buf"]
        key, reset_key = dev["key"], dev["reset_key"]
        ep = last_saved = int(host["ep"])
        result.episode_reward = list(host["episode_reward"])
        result.episode_leak = list(host["episode_leak"])
        result.episode_violation = list(host["episode_violation"])
        result.states_explored = list(host["states_explored"])
        seen = set(host["seen"])

    def _save(ep_now: int) -> None:
        TS.save_train_checkpoint(
            checkpoint_dir, ep_now,
            dict(params=params, opt_state=opt_state, buf=buf,
                 key=key, reset_key=reset_key),
            dict(ep=ep_now, meta=meta,
                 episode_reward=result.episode_reward,
                 episode_leak=result.episode_leak,
                 episode_violation=result.episode_violation,
                 states_explored=result.states_explored,
                 seen=sorted(seen)),
        )

    while ep < episodes:
        # chunk-boundary checkpoint: the state right here fully determines
        # the remainder of the run (keys are split inside the chunk)
        if (checkpoint_dir and checkpoint_every
                and (last_saved is None or ep - last_saved >= checkpoint_every)):
            _save(ep)
            last_saved = ep
        if resample_positions:
            key, reset_key = jax.random.split(key)
        rkeys = R.episode_reset_keys(reset_key, num_envs, resample_positions)
        key, ksub, ku = jax.random.split(key, 3)
        akeys = jax.random.split(ksub, num_envs)
        rkeys = PD.shard_population(rkeys, mesh, num_envs)
        akeys = PD.shard_population(akeys, mesh, num_envs)

        # whole chunk (reset/rollout/buffer/updates/metric reduction) in one
        # buffer-donated dispatch. Warmup rounds UP to chunk granularity: the
        # traced `train` flag stays False until the chunk that starts
        # at/past the boundary (exact at num_envs=1), and the update scan is
        # additionally cond-gated on buffer fill - on device, no size sync.
        train = jnp.asarray(ep >= warmup_episodes)
        params, opt_state, buf, metrics = chunk(
            params, opt_state, buf, rkeys, akeys, ku, train, scenario
        )
        _reduced_chunk_metrics(result, seen, jax.device_get(metrics), ep,
                               episodes, num_envs)
        ep += num_envs

    if checkpoint_dir and last_saved != ep:
        _save(ep)

    result.params = params  # type: ignore[attr-defined]
    result.trace_count = chunk.trace_count[0]
    return result


def evaluate_sac(env: MHSLEnv, params, cfg: SAC.SACConfig, episodes: int = 20,
                 seed: int = 1000, scenario=None) -> Dict[str, float]:
    """Policy evaluation: all ``episodes`` run as one vmapped population
    (fresh geometry per episode, matching the seed's evaluation draw).
    ``scenario`` sweeps evaluation physics without recompiling; for a
    whole grid in one call use ``repro.core.scenario.evaluate_population``.
    """
    key = jax.random.PRNGKey(seed)
    k_reset, k_act = jax.random.split(key)
    rollout = R.make_batched_rollout(
        env, R.sac_policy(env.action_dims, cfg), cfg.hist_len
    )
    st0 = R.make_batched_reset(env)(jax.random.split(k_reset, episodes),
                                    scenario)
    _, traj = rollout(params, st0, jax.random.split(k_act, episodes), scenario)
    return {
        "reward": float(jnp.sum(traj["reward"])) / episodes,
        "leak": float(jnp.sum(traj["leak"])) / episodes,
    }


def rollout_plan(env: MHSLEnv, params, cfg: SAC.SACConfig, seed: int = 7):
    """Roll the trained policy through one episode and read off its plan.

    Returns ``(boundaries, devices, leaked, t_r, e_r)``: the split plan's
    cumulative cut points and stage devices at the end of the episode,
    the information leaked along the way, and the time and energy budget
    left at its end (negative = budget overrun).
    """
    key = jax.random.PRNGKey(seed)
    st = env.reset(jax.random.PRNGKey(0))
    pair_dim = env.obs_dim + A.flat_dim(env.action_dims)
    hist = jnp.zeros((cfg.hist_len, pair_dim))
    hmask = jnp.zeros((cfg.hist_len,))
    leaked = 0.0
    for _ in range(env.episode_len):
        key, ka, ks = jax.random.split(key, 3)
        obs = env.observe(st)
        masks = env.action_masks(st)
        a = SAC.select_action(params, ka, obs, hist, hmask, masks,
                              env.action_dims, cfg)
        pair = jnp.concatenate([obs, A.onehot(a, env.action_dims)])
        hist = jnp.roll(hist, -1, axis=0).at[-1].set(pair)
        hmask = jnp.roll(hmask, -1).at[-1].set(1.0)
        st, _, _, info = env.step(st, a, ks)
        leaked += float(info["leak"])
    return (
        tuple(int(b) for b in np.asarray(st.boundaries)),
        tuple(int(d) for d in np.asarray(st.stage_dev)),
        leaked,
        float(st.t_r),
        float(st.e_r),
    )
