"""What every traffic driver shares: the driver lookup, stratified draws,
and the request and job records the harness drives.

A traffic mix lives in ``bench/traffic/<mix>.json`` and holds only
parameters. Each of its parts names the driver that reads it::

  serve      {"driver": "<name>", ...}   requests to the scheduler
  finetune   {"driver": "<name>", ...}   fine-tune jobs (ingest, adapt,
             release) with ``cohort``, ``samples``, ``seq``,
             ``ingest_batch``, ``epochs`` and ``batch_per_tenant``
  scheduler  the request scheduler's limits (``attach_scheduler``)
  runtime    further ``SessionRuntime`` options (``pool_compress``,
             ``pool_slots``, ``decode_fuse``, ...); ``mesh``:
             ``{"shape": [...], "axes": [...]}``

A driver is ``bench/traffic/<name>.py``, found by name. A serve driver
has ``stream(params, *, vocab, seed, seconds, max_batch)``, returning an
object with ``tenants`` (the adapted tenants to register), ``open`` and,
for an open loop, ``planned`` (``Planned`` requests with due times in
``[0, seconds)``), for a closed loop ``outstanding`` and ``next()``. A
fine-tune driver has ``stream(params, *, vocab, seed)``, returning an
object whose ``next()`` gives the next ``Job``. A new traffic shape is a
new driver file and a mix that names it.

The drivers here make every seed the same work in another order: lengths,
gaps and tenant counts are stratified quantiles of their distributions
(the value at ``(i + 0.5) / n`` for ``i < n``), and the seed only permutes
them and draws the token ids.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
from statistics import NormalDist
from typing import Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def driver(name: str, root: str = os.path.dirname(HERE)):
    """The traffic driver ``bench/traffic/<name>.py`` under ``root``."""
    path = os.path.join(root, "bench", "traffic", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_traffic_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws of an integer length distribution, ascending:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
    ``{"dist": "uniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if kind == "uniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        return np.floor(lo + u * (hi - lo + 1)).astype(np.int64)
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.round(spec["median"] * np.exp(spec["sigma"] * z))
        return np.clip(v, spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def stratified_counts(weights: np.ndarray, n: int) -> np.ndarray:
    """Integer counts summing to n in proportion to ``weights`` (largest
    remainder)."""
    p = weights / weights.sum()
    raw = p * n
    counts = np.floor(raw).astype(np.int64)
    short = n - counts.sum()
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:short]] += 1
    return counts


def tenant_names(n: int) -> list[str]:
    return [f"tenant-{k:03d}" for k in range(n)]


def tenant_draws(serve: dict, n: int, rng: np.random.Generator) -> list:
    """n tenant labels (None = base model) with stratified Zipf counts over
    ``tenants`` adapted tenants (``zipf_s``) plus ``base_share`` base."""
    k = int(serve["tenants"])
    zipf = 1.0 / np.arange(1, k + 1) ** float(serve.get("zipf_s", 0.0))
    base = float(serve.get("base_share", 0.0))
    weights = np.concatenate([[base / max(1e-12, 1 - base) * zipf.sum()], zipf])
    counts = stratified_counts(weights, n)
    names = [None] + tenant_names(k)
    labels = [names[i] for i, c in enumerate(counts) for _ in range(c)]
    return [labels[i] for i in rng.permutation(n)]


@dataclasses.dataclass
class Planned:
    """One request as a driver plans it."""

    due: float                    # seconds after the window opens (open loop)
    tenant: Optional[str]
    prompt: np.ndarray            # (len,) int32
    max_new: int


@dataclasses.dataclass
class Job:
    """One fine-tune job: a new tenant's samples, in ingest batches."""

    tenant: str
    batches: list                 # [(tokens (b, S) int32, labels (b, S) int32)]
