"""A model comes into the benchmark as new files only. ``tiny.make_root``
writes a model module and its plain reference (``tiny_window_gqa``:
RMSNorm with no bias, 4 query heads over 2 KV heads of size 32, which is
not hidden / heads, and a period of a sliding-window and a global layer),
a configuration that names it and one serve cell, beside a copy of the
repository's ``bench``. The cell runs end to end on the CPU through the
harness's test entry (``run_cell``; the command itself refuses a CPU)."""

import filecmp
import gc
import os
import time

import pytest

from bench import check as C
from bench import counts
from bench import drive
from bench import model as M
from bench import run as BR
from bench.tests import tiny

CELL = "tiny-window-gqa-chat"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    d = tmp_path_factory.mktemp("modelroot")
    tiny.make_root(str(d))
    return str(d)


def test_cell_on_new_model_is_correct(root):
    cell = BR.load_cell(root, CELL)
    res, lines = BR.run_cell(cell, 2**31 + 5, 2.0, False, t0=time.perf_counter())
    assert res["correct"], lines
    assert res["failed"] == 0 and res["attempted"] > 0
    gap = res["checks"]["serve_gap"]
    assert gap["value"] <= gap["limit"] == tiny.WINDOW_GQA_LIMITS["limits"]["serve_gap"]
    assert set(res["metrics"]) == {m["name"] for m in cell["end_to_end"]}


def test_new_model_control_fails(root):
    """The reference in float8 in the program's place reads over the
    limit on the same served tokens, while the program reads under it."""
    cell = BR.load_cell(root, CELL)
    s = drive.Session(cell["config"], cell["mix"], 2**31 + 7, 2.0, root=root)
    s.warm_up()
    s.run(2.0)
    sample = C.sample_served(s, int(cell["limits"]["sample_tokens"]))
    params = s.free_program()
    gc.collect()
    sc = s.sched_kw
    got = C.serve_readings(params, s, sample, sc["max_prompt"] + sc["max_new_cap"],
                           control=True)
    limit = cell["limits"]["limits"]["serve_gap"]
    assert got["serve_gap"] <= limit < got["control_serve_gap"], got


def test_counts_forward_to_new_model(root):
    """``bench/counts.py`` takes the model's own counts: the window layers
    attend at most 8 keys, and heads are 32 wide."""
    cfg = BR.load_cell(root, CELL)["config"]
    M.model(cfg, root)
    per_key = 4.0 * 4 * 32                        # QK^T and PV, 4 heads of 32
    assert counts.attention_flops(cfg, 20) == per_key * (8 + 20 + 8 + 20)
    mm = 4 * (64 * (4 + 2 * 2) * 32 + 4 * 32 * 64 + 3 * 64 * 128)
    assert counts.matmul_params(cfg) == mm
    # window layers: 8 * 9 / 2 pairs, then 8 keys for each of 12 more tokens
    pairs = 2 * (36 + 12 * 8) + 2 * (20 * 21 / 2)
    assert counts.prefill_flops(cfg, 20) == (
        2.0 * mm * 20 + per_key * pairs + counts.skip_flops(cfg)
        + counts.readout_flops(cfg))


def test_model_added_by_new_files_only(root):
    """Every file the repository's ``bench`` holds is byte-identical in the
    copy; the model's module, reference and configuration are new."""
    src = os.path.join(tiny.ROOT, "bench")
    for dirpath, _, files in os.walk(src):
        if "__pycache__" in dirpath or os.sep + "tests" in dirpath:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), src)
            assert filecmp.cmp(os.path.join(src, rel),
                               os.path.join(root, "bench", rel), shallow=False), rel
    for rel in (f"models/{tiny.WINDOW_GQA}.py", f"reference/{tiny.WINDOW_GQA}.py",
                "configs/tiny-window-gqa.json"):
        assert not os.path.exists(os.path.join(src, rel)), rel
        assert os.path.exists(os.path.join(root, "bench", rel)), rel
