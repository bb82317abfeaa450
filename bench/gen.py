"""The one traffic generator: a traffic file's parameters plus a seed ->
the requests (serving) or batches (training) a run drives.

Every seed gets the same requests in size and time: prompt and answer
lengths and arrival times are drawn once from the mix's own
``shape_seed``; the run's ``--seed`` draws the token ids (and, in the
harness, the weights). So two seeds do the same work, and the spread
between seeds is the system's, not the traffic's. (Permuting the lengths
over the arrival times per seed moved the 95th-percentile time to first
token of ``serve_prompt`` by 16% between seeds, against 1% between two
runs of one seed.)

Distributions (each a dict with ``kind``):

* ``lognormal``: ``median``, ``sigma``, clipped to ``[min, max]``;
* ``gamma`` inter-arrival gaps: ``shape`` (CV = 1/sqrt(shape); 1 is
  Poisson), mean gap ``1 / rate_per_s``;
* ``poisson``: gamma with shape 1.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Req:
    rid: int
    due: float          # seconds after the window opens
    prompt: np.ndarray  # (plen,) int32
    out_len: int


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["kind"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['kind']!r}")
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gaps(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    shape = {"poisson": 1.0, "gamma": spec.get("shape")}.get(spec["kind"])
    if shape is None:
        raise ValueError(f"unknown arrival process {spec['kind']!r}")
    return rng.gamma(shape, 1.0 / (shape * spec["rate_per_s"]), n)


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """Every request due in ``[0, seconds)``: ``round(rate * seconds)`` of
    them. Request ``i`` is due after the first ``i`` gaps, all gaps scaled
    by one factor so that the ``n`` of them fill the window exactly."""
    n = max(int(round(mix["arrivals"]["rate_per_s"] * seconds)), 1)
    base = np.random.default_rng(mix["shape_seed"])
    plen = lengths(mix["prompt_len"], n, base)
    olen = lengths(mix["output_len"], n, base)
    gap = gaps(mix["arrivals"], n, base)
    rng = np.random.default_rng(seed)
    due = (np.cumsum(gap) - gap) * (seconds / gap.sum())
    return [Req(rid=i, due=float(due[i]),
                prompt=rng.integers(0, vocab, int(plen[i])).astype(np.int32),
                out_len=int(olen[i]))
            for i in range(n)]


def batches(mix: dict, seed: int, vocab: int) -> tuple[np.ndarray, np.ndarray]:
    """``pool`` training batches of (rows, seq) tokens and labels; every
    row of every batch differs."""
    b = mix["batch"]
    rng = np.random.default_rng(seed)
    shape = (b["pool"], b["rows"], b["seq"])
    tokens = rng.integers(0, vocab, shape).astype(np.int32)
    labels = rng.integers(0, vocab, shape).astype(np.int32)
    return tokens, labels
