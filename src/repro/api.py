"""Single-import facade over the repro stack.

``repro.api`` re-exports the stable entry points of every subsystem so
drivers (examples, benchmarks, notebooks) depend on ONE module instead
of deep submodule paths:

* **RL planning** - :func:`train_sac` (single env),
  :func:`train_population` (vectorized scenario batch),
  :func:`score_plans` / :func:`make_split_oracle` (exhaustive scoring).
* **Execution** - :func:`pipeline_step_fn` (1F1B training executor),
  :class:`ServingService` (continuous-batching inference).
* **Leakage** - :func:`evaluate_leakage` with :class:`AnalyticLeakage`
  (the paper's closed-form Theorem 1 / Eq. 30 model) or
  :class:`EmpiricalLeakage` (the trained FSHA-style attacker's measured
  per-boundary values, :func:`train_empirical_model`).
* **Model stack** - configs, params, train step, data, optimizers,
  checkpointing, used by the quickstart and the pipeline drivers.
* **Fault tolerance** - :class:`FaultSchedule` /
  :func:`sample_fault_schedule` (seeded replayable outages),
  :func:`degrade_scenario` (fold hop degradation into scenario
  physics), consumed by ``ServingService.run(faults=...)`` and the
  kill-and-resume chaos harness (``repro.launch.chaos``).
"""
from __future__ import annotations

from repro.attack import (AttackConfig, capture_weight,
                          train_attacker_population, train_empirical_model)
from repro.checkpoint import load_pytree, save_pytree
from repro.configs import get_config
from repro.core.agents.action_space import flat_dim, onehot
from repro.core.agents.loops import rollout_plan, train_sac
from repro.core.agents.sac import SACConfig, select_action
from repro.core.channel import NetworkConfig
from repro.core.env import MHSLEnv
from repro.core.faults import (FaultClock, FaultSchedule, degrade_scenario,
                               fault_free, make_schedule, reference_schedule,
                               sample_fault_schedule)
from repro.core.leakage import (AnalyticLeakage, EmpiricalLeakage,
                                LeakageModel, evaluate_leakage,
                                plan_hop_geometry)
from repro.core.pipeline import (PipelineConfig, make_stage_mesh,
                                 pipeline_step_fn)
from repro.core.profiles import transformer_profile
from repro.core.scenario import (ScenarioParams, evaluate_population,
                                 train_population)
from repro.core.splitting import make_plan_scorer, score_plans
from repro.data import synthetic_stream
from repro.models import init_params, make_train_step
from repro.optim import adamw, linear_warmup_cosine
from repro.serving import ServeConfig, ServingService


def make_split_oracle(env: MHSLEnv):
    """Batched exhaustive split-plan scorer for ``env`` (the serving
    re-planner's oracle): ``oracle(p_tx, decoy, positions) -> scores``
    over every (boundaries x devices) candidate. Facade wrapper over
    :meth:`repro.core.env.MHSLEnv.make_split_oracle`."""
    return env.make_split_oracle()


__all__ = [
    "AnalyticLeakage",
    "AttackConfig",
    "EmpiricalLeakage",
    "FaultClock",
    "FaultSchedule",
    "LeakageModel",
    "MHSLEnv",
    "NetworkConfig",
    "PipelineConfig",
    "SACConfig",
    "ScenarioParams",
    "ServeConfig",
    "ServingService",
    "adamw",
    "capture_weight",
    "degrade_scenario",
    "evaluate_leakage",
    "evaluate_population",
    "fault_free",
    "flat_dim",
    "get_config",
    "init_params",
    "linear_warmup_cosine",
    "load_pytree",
    "make_plan_scorer",
    "make_schedule",
    "make_split_oracle",
    "make_stage_mesh",
    "make_train_step",
    "onehot",
    "pipeline_step_fn",
    "plan_hop_geometry",
    "reference_schedule",
    "rollout_plan",
    "sample_fault_schedule",
    "save_pytree",
    "score_plans",
    "select_action",
    "synthetic_stream",
    "train_attacker_population",
    "train_empirical_model",
    "train_population",
    "train_sac",
    "transformer_profile",
]
