"""Each cell's traffic end to end on the CPU at a tiny size, through the
harness's test entry (``run_cell``; the command itself refuses a CPU):
a cell added only as new files and entries runs, its per-layer metrics
read, and ``correct`` comes out false under each fault the cell can have.
"""

import jax
import numpy as np
import pytest

from bench import peaks as P
from bench import run as BR
from bench.tests import tiny

CELLS = ["tiny-stablelm-chat", "tiny-musicgen-finetune", "tiny-musicgen-clips",
         "tiny-chat-bursty", "tiny-crowd", "tiny-serve-adapt"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    d = tmp_path_factory.mktemp("benchroot")
    tiny.make_root(str(d))
    return str(d)


@pytest.fixture
def cpu_peaks(monkeypatch):
    """Let the per-layer readers run on the CPU (their numbers are not
    device metrics and are never reported)."""
    monkeypatch.setitem(P.PEAKS, "cpu", dict(P.PEAKS["TPU v5 lite"]))


def run(root, workload, trace=False, seconds=2.0):
    cell = BR.load_cell(root, workload)
    import time
    return BR.run_cell(cell, 2**31 + 11, seconds, trace, t0=time.perf_counter())


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(root, workload, cpu_peaks):
    res, lines = run(root, workload)
    assert res["correct"], lines
    assert res["failed"] == 0 and res["attempted"] > 0
    cell = BR.load_cell(root, workload)
    assert set(res["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    res, _ = run(root, workload, trace=True)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(res["metrics"]) <= names
    # Host-side readers always find something; device-trace ones need a chip.
    host = {m["name"] for m in cell["per_layer"] if m["source"] != "device_trace"}
    assert host <= set(res["metrics"])
    assert res["device"]["window_s"] > 0


def test_serve_token_altered_is_caught(root, monkeypatch):
    """A token altered where the scheduler produces it."""
    from repro.core import scheduler as S

    orig = S.RequestScheduler._harvest

    def harvest(self, shard, admitted, out):
        orig(self, shard, admitted, out)
        for req, _ in admitted:
            if req.tokens:
                req.tokens[0] = (req.tokens[0] + 1) % self.rt.cfg.vocab_size

    monkeypatch.setattr(S.RequestScheduler, "_harvest", harvest)
    res, lines = run(root, "tiny-stablelm-chat")
    assert not res["correct"], lines
    assert res["checks"]["serve_gap"]["value"] > res["checks"]["serve_gap"]["limit"]


def test_serve_wrong_slot_is_caught(root, monkeypatch):
    """Every adapted tenant's rows read the pool's zero slot: tenants are
    served the base model (``bench/control.py --fault slot`` on the chip)."""
    from repro.core import adapter_pool as AP

    monkeypatch.setattr(
        AP.ShardedAdapterPool, "lookup_local",
        lambda self, s, tenants: np.full((len(tenants),), AP.ZERO_SLOT, np.int32))
    res, lines = run(root, "tiny-stablelm-chat")
    assert not res["correct"], lines
    assert res["checks"]["serve_gap"]["value"] > res["checks"]["serve_gap"]["limit"]


def _patch_epoch(monkeypatch, make):
    from repro.core import fleet_finetune as FF
    from repro.core import runtime as RT

    monkeypatch.setattr(RT, "_FN_CACHE", {})
    orig = FF.make_fleet_cached_epoch
    monkeypatch.setattr(FF, "make_fleet_cached_epoch",
                        lambda *a, **k: make(orig(*a, **k), *a, **k))


def test_finetune_state_unchanged_is_caught(root, monkeypatch):
    def make(epoch, *a, **k):
        def f(params, stacked, opt_state, cache, idx_mat, row_tenant):
            _, _, losses = epoch(params, stacked, opt_state, cache, idx_mat, row_tenant)
            return stacked, opt_state, losses
        return f

    _patch_epoch(monkeypatch, make)
    res, lines = run(root, "tiny-musicgen-finetune")
    assert not res["correct"], lines
    assert res["checks"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_finetune_half_batch_is_caught(root, monkeypatch):
    """Each step's mean over the first half of every tenant's rows."""
    def make(epoch, cfg, sl, opt, n, **k):
        def f(params, stacked, opt_state, cache, idx_mat, row_tenant):
            bpt = idx_mat.shape[1] // n
            keep = np.concatenate([np.arange(t * bpt, t * bpt + bpt // 2)
                                   for t in range(n)])
            return epoch(params, stacked, opt_state, cache, idx_mat[:, keep],
                         row_tenant[keep])
        return f

    _patch_epoch(monkeypatch, make)
    res, lines = run(root, "tiny-musicgen-finetune")
    assert not res["correct"], lines


def test_new_cells_are_files_only(root):
    """The tiny cells above came in as new files and new BENCHMARK.json
    entries, one of them with a traffic driver of its own
    (``bench/traffic/tiny_crowd.py``): every file the benchmark already
    had is unchanged."""
    import filecmp
    import os

    src = os.path.join(tiny.ROOT, "bench")
    for dirpath, _, files in os.walk(src):
        if "__pycache__" in dirpath or os.sep + "tests" in dirpath:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), src)
            assert filecmp.cmp(os.path.join(src, rel),
                               os.path.join(root, "bench", rel), shallow=False), rel
    for w in CELLS:
        cell = BR.load_cell(root, w)
        assert cell["workload"]["config"] == "tiny"


def test_mix_runtime_options_reach_the_runtime(root):
    """A mix's ``runtime`` part is passed to ``SessionRuntime``: a new mix
    can set the pool's compression (or a mesh) as data."""
    from bench import drive

    cell = BR.load_cell(root, "tiny-stablelm-chat")
    mix = dict(cell["mix"], runtime={"pool_compress": "nf4", "pool_slots": 12})
    s = drive.Session(cell["config"], mix, 5, 1.0, root=root)
    assert s.rt.pool.compress == "nf4"
    assert s.rt.pool.shards[0].n_slots == 12
