"""Serving knobs + the config-driven launcher contract.

``ServeConfig`` is the single source of truth for both entrypoints
(``examples/serve.py`` and ``launch/serve.py``): the launcher loads a
JSON file (``--config serve.json``), applies CLI ``--key value``
overrides on top, and hands the result to
:class:`repro.serving.service.ServingService` - the same
config-file-plus-overrides shape as the exemplar split-deployment
launchers, so a deployment is a reviewable artifact instead of a shell
history.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple


@dataclass(frozen=True)
class ServeConfig:
    """Engine + scheduler + trace knobs.

    Scheduler knobs: ``num_slots`` is the continuous batch width (N
    draining microbatch slots), ``arrival_slots`` bounds admissions per
    tick (A), ``decode_chunk`` is tokens decoded per engine tick (the
    admission latency/dispatch-overhead trade: a slot freed mid-chunk
    re-admits only at the next tick).
    """

    arch: str = "qwen2_5_3b"      # repro.configs module name
    reduced: bool = True          # .reduced() arch (CPU-sized)
    num_layers: Optional[int] = None  # override depth (benchmarks)
    num_slots: int = 8
    arrival_slots: int = 4
    # longest admitted prompt; a tick's arrivals pad to the smallest of
    # four classes up to this length (prompt_pad / 8, / 4, / 2, itself)
    prompt_pad: int = 32
    max_new: int = 32             # gen_buf depth / decode scan bound
    decode_chunk: int = 8
    temperature: float = 0.0
    seed: int = 0
    # split serving: None = single device; else the split plan's
    # cumulative cut points, run on a stage mesh of len(boundaries)
    # devices with per-stage KV rings.
    boundaries: Optional[Tuple[int, ...]] = None
    compute_dtype: str = "float32"
    wire_dtype: Optional[str] = None
    # online re-planner cadence: re-score the split plan every K engine
    # ticks (0 = off). Re-plans are recorded, not applied mid-flight
    # (cache migration between stages is out of scope).
    replan_every: int = 0
    # failure handling (active when a FaultSchedule is passed to run()):
    # deadline_s is the default per-request completion budget after
    # arrival (0 = no deadline; Request.deadline overrides); a failed
    # tick retries up to max_retries times with exponential backoff
    # starting at retry_backoff_s before evicting in-flight slots;
    # fault_tick_s > 0 drives the FaultClock deterministically
    # (schedule time = tick * fault_tick_s, independent of wall clock).
    deadline_s: float = 0.0
    max_retries: int = 3
    retry_backoff_s: float = 0.01
    fault_tick_s: float = 0.0

    def model_config(self):
        import importlib

        mod = importlib.import_module(f"repro.configs.{self.arch}")
        cfg = mod.CONFIG.reduced() if self.reduced else mod.CONFIG
        if self.num_layers is not None:
            cfg = dataclasses.replace(cfg, num_layers=self.num_layers)
        return cfg

    def init_params(self):
        """Random weights from ``seed``, built in ``compute_dtype`` under
        ``jax.jit``: the f32 draws fuse into their casts, so a bf16 model
        at published widths never holds an f32 copy on the device."""
        import jax
        import jax.numpy as jnp

        from repro.models.model import init_params

        return jax.jit(init_params, static_argnums=(1, 2))(
            jax.random.PRNGKey(self.seed), self.model_config(),
            jnp.dtype(self.compute_dtype))

    @classmethod
    def load(cls, path: Optional[str] = None,
             overrides: Optional[dict] = None) -> "ServeConfig":
        """JSON file -> ServeConfig, with ``overrides`` applied on top.

        Unknown keys are an error (a typoed knob must not silently run
        the defaults)."""
        raw = {}
        if path is not None:
            with open(path) as f:
                raw.update(json.load(f))
        raw.update(overrides or {})
        fields = {f.name: f for f in dataclasses.fields(cls)}
        unknown = sorted(set(raw) - set(fields))
        if unknown:
            raise KeyError(f"unknown ServeConfig keys: {unknown}")
        if "boundaries" in raw and raw["boundaries"] is not None:
            raw["boundaries"] = tuple(int(b) for b in raw["boundaries"])
        return cls(**raw)

    @staticmethod
    def parse_override(key: str, value: str):
        """CLI override coercion: ``--num_slots 16``, ``--boundaries
        2,4``, ``--reduced false``."""
        fields = {f.name: f for f in dataclasses.fields(ServeConfig)}
        if key not in fields:
            raise KeyError(f"unknown ServeConfig key: {key}")
        if key == "boundaries":
            return tuple(int(x) for x in value.split(","))
        typ = fields[key].type
        if value.lower() in ("none", "null"):
            return None
        if "bool" in str(typ):
            return value.lower() in ("1", "true", "yes")
        if "int" in str(typ):
            return int(value)
        if "float" in str(typ):
            return float(value)
        return value
