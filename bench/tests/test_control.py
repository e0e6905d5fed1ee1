"""The control at a size a test run holds: the plain reference computed
in float8, put in the program's place, comes out not correct against the
cell's own limits, while the program at the same size comes out correct.
On the chip the same readings are taken at each cell's own size by
``bench/control.py``."""

import gc

import pytest

from bench import check as C
from bench import drive
from bench import run as BR
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    d = tmp_path_factory.mktemp("controlroot")
    tiny.make_root(str(d))
    return str(d)


def _fails(readings: dict, limits: dict) -> bool:
    return any(readings[k] > v for k, v in limits.items())


@pytest.mark.parametrize("workload", ["tiny-stablelm-chat", "tiny-musicgen-clips"])
def test_serve_control_fails(root, workload):
    cell = BR.load_cell(root, workload)
    s = drive.Session(cell["config"], cell["mix"], 2**31 + 3, 2.0)
    s.warm_up()
    s.run(2.0)
    sample = C.sample_served(s, int(cell["limits"]["sample_tokens"]))
    params = s.free_program()
    gc.collect()
    sc = s.sched_kw
    got = C.serve_readings(params, s, sample, sc["max_prompt"] + sc["max_new_cap"],
                           control=True)
    limits = cell["limits"]["limits"]
    assert not _fails(got, limits), got
    assert _fails({"serve_gap": got["control_serve_gap"]}, limits), got


def test_finetune_control_and_half_batch_fail(root):
    cell = BR.load_cell(root, "tiny-musicgen-finetune")
    s = drive.Session(cell["config"], cell["mix"], 2**31 + 3, 1.0)
    s.warm_up()
    params = s.free_program()
    ref = C.job_reference(params, s)
    limits = cell["limits"]["limits"]
    assert not _fails(C.job_readings(s, ref), limits)
    fp8 = C.job_reference(params, s, precision="fp8")
    assert _fails(C.job_readings(s, ref, prog=fp8), limits)
    half = C.job_reference(params, s, keep_rows=0.5)
    assert _fails(C.job_readings(s, ref, prog=half), limits)
