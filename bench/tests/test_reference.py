"""The plain reference against the program at reduced width, in float32:
serve logits, and one cached adapt epoch against a recompute-from-forward
reference."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check as C
from bench import drive
from bench import weights as W
from bench.reference import adapt as RA
from bench.reference import lm as RL
from bench.tests.tiny import TINY_CONFIG

import json
import os

from bench.tests.tiny import ROOT


def tiny_cfg(base: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", f"{base}.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY_CONFIG, torch_dtype="float32")
    return cfg


@pytest.mark.parametrize("base", ["stablelm-1.6b", "musicgen-medium"])
def test_serve_logits_match_program(base):
    from repro.core import lm_skiplora as SL
    from repro.models.lm import lm_forward, readout

    cfg = tiny_cfg(base)
    mcfg = drive.model_config(cfg)
    params = W.make_params(cfg, 3)
    ad = W.make_adapters(cfg, 3, 2)
    a, b = ad["A"][1], ad["B"][1]
    tokens = jax.random.randint(jax.random.key(0), (12,), 0, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        out = lm_forward(params, mcfg, tokens[None], mode="train",
                         adapters=SL.adapters_to_stack({"A": a, "B": b}, mcfg))
        prog = readout(params, mcfg, out["h"])[0]
    ref = RL.sequence_logits(params, cfg, tokens, a, b)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(prog - ref))) <= 1e-4 * scale
    # The adapters move the logits: the comparison sees the skip term.
    base_ref = RL.sequence_logits(params, cfg, tokens, 0 * a, 0 * b)
    assert float(jnp.max(jnp.abs(base_ref - ref))) > 1e-2 * scale


def test_cached_adapt_epoch_matches_recompute():
    """The runtime's first adapt epoch from the skip cache (two steps)
    against the reference's two steps from a recomputed forward."""
    cfg = tiny_cfg("musicgen-medium")
    mix = {"finetune": {"driver": "jobs", "cohort": 2, "samples": 4, "seq": 8,
                        "ingest_batch": 2, "epochs": 3, "batch_per_tenant": 2}}
    with jax.default_matmul_precision("highest"):
        s = drive.Session(cfg, mix, 5, 1.0)
        s.warm_up()
    ref = C.job_reference(s.params, s)
    got = C.job_readings(s, ref)
    assert got["loss_gap"] < 1e-5
    assert got["moment_gap"] < 1e-3
    assert got["delta_gap"] < 1e-3
    # A step that leaves the adapters unchanged reads 1 on delta_gap.
    snap = s.check_job["snapshot"]
    a0 = jax.tree.map(np.asarray, ref[0])
    for i, st in enumerate(snap["state"]):
        st["adapters"] = {k: a0[k][i] for k in ("A", "B")}
    assert C.job_readings(s, ref)["delta_gap"] == pytest.approx(1.0)
