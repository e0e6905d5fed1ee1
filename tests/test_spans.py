"""Host spans (``core/spans.py``): the ring, the profiler's copy of each
span, the spans a scheduler tick, an ingest and an adapt open, the
first-token stamp, and the compile marks of every jitted body.

All on the CPU, in one file: the profiler runs one session at a time.
"""

import ast
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core import lm_skiplora as SL
from repro.core import spans as SP
from repro.core.runtime import SessionRuntime
from repro.models.lm import init_lm

CORE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src", "repro", "core")


@pytest.fixture(scope="module")
def cfg():
    return reduce_config(get_config("stablelm-1.6b"))


@pytest.fixture(scope="module")
def params(cfg):
    return init_lm(jax.random.key(0), cfg)


def make_runtime(cfg, params, n_t=2):
    sl = SL.SkipLoRAConfig(rank=4, mode="full", cache_dtype="float32")
    return SessionRuntime(cfg, sl, params, max_tenants=n_t,
                          samples_per_tenant=4, seq=8, lr=1e-2)


def data(cfg, n_t=2, n=4, seed=1):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return (jax.random.randint(k1, (n_t, n, 8), 0, cfg.vocab_size),
            jax.random.randint(k2, (n_t, n, 8), 0, cfg.vocab_size))


def since(t0):
    return [r for r in SP.RECORDER.records() if r.start >= t0]


def kids(records, parent):
    return sorted((r for r in records if r.parent == parent.id),
                  key=lambda r: r.start)


def one(records, name):
    got = [r for r in records if r.name == name]
    assert got, f"no {name} span"
    return got


# -- the recorder ------------------------------------------------------------


def test_nesting_and_parent_ids():
    rec = SP.Recorder()
    with rec.span("outer", a=1) as outer:
        with rec.span("inner") as inner:
            rec.mark("compile.x")
        later = rec.span("later").open()
        outer.set(rids=(3, 4))
        # ``later`` is still open: closing ``outer`` closes it first.
    by = {r.name: r for r in rec.records()}
    assert by["outer"].parent == 0
    assert by["inner"].parent == outer.id and by["later"].parent == outer.id
    assert by["compile.x"].parent == inner.id
    assert by["compile.x"].start == by["compile.x"].end
    assert by["outer"].attrs == {"a": 1, "rids": (3, 4)}
    assert by["outer"].start <= by["inner"].start <= by["inner"].end
    assert by["later"].end <= by["outer"].end
    later.close()                      # closing twice is harmless
    assert len(rec.records()) == 4 and rec._stack() == []
    with rec.span("after") as after:
        pass
    assert after.parent == 0


def test_ring_bound_and_dropped():
    rec = SP.Recorder(maxlen=4)
    opened = []
    for i in range(6):
        with rec.span(f"s{i}") as sp:
            opened.append(sp)
    names = [r.name for r in rec.records()]
    assert names == ["s2", "s3", "s4", "s5"]
    assert rec.dropped == 2
    # A window that starts after the newest lost record is whole.
    assert rec.lost_until == opened[1].start < rec.records()[0].start


# -- the scheduler tick ------------------------------------------------------


def adapted(cfg, params):
    rt = make_runtime(cfg, params)
    toks, labs = data(cfg)
    for t in range(2):
        rt.ingest(f"u{t}", toks[t, :2], labs[t, :2])
    rt.adapt(epochs=1, batch_per_tenant=2, key=jax.random.key(3))
    return rt


def test_tick_spans_in_order_and_first_token_stamp(cfg, params):
    rt = adapted(cfg, params)
    rt.attach_scheduler(max_batch=4, max_prompt=8, max_new_cap=8, chunk=2,
                        admit_bucket=2)
    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    reqs = [rt.enqueue_serve(t, rng.integers(0, cfg.vocab_size, 5), max_new=5)
            for t in ("u0", "u1", None)]
    rt.drain()
    recs = since(t0)
    ticks = one(recs, "sched.step")
    first = ticks[0]
    seq = [r.name for r in kids(recs, first)]
    assert seq[:4] == ["sched.plan", "sched.launch", "sched.wait",
                       "sched.harvest"], seq
    launch = kids(recs, first)[1]
    assert launch.attrs["kind"] == "admit" and launch.attrs["shard"] == 0
    assert set(launch.attrs["rids"]) == {reqs[0].rid, reqs[1].rid}
    assert set(first.attrs["rids"]) == set(launch.attrs["rids"])
    kinds = {r.attrs["kind"] for r in recs if r.name == "sched.launch"}
    assert kinds == {"admit", "step"}
    for r in recs:                       # every child closes inside its tick
        if r.name in ("sched.wait", "sched.harvest"):
            p = next(t for t in ticks if t.id == r.parent)
            assert p.start <= r.start <= r.end <= p.end
    for r in reqs:
        assert r.started_at <= r.first_token_at <= r.finished_at
    # The stamp is when the host holds the first token: after the wait of
    # the admitting tick.
    wait = kids(recs, first)[2]
    assert wait.end <= reqs[0].first_token_at


def test_scheduler_ingest_runs_in_a_tick_span(cfg, params):
    rt = adapted(cfg, params)
    sched = rt.attach_scheduler(max_batch=2, max_prompt=8, max_new_cap=4)
    toks, labs = data(cfg, seed=5)
    t0 = time.perf_counter()
    req = rt.enqueue_ingest("u0", toks[0, :2], labs[0, :2])
    sched.step()
    assert req.done
    recs = since(t0)
    (tick,) = one(recs, "sched.step")
    (ing,) = one(recs, "sched.ingest")
    assert ing.parent == tick.id
    (inner,) = one(recs, "ingest")
    assert inner.parent == ing.id


# -- ingest and adapt --------------------------------------------------------


def test_ingest_and_adapt_spans(cfg, params):
    rt = make_runtime(cfg, params)
    toks, labs = data(cfg, seed=7)
    t0 = time.perf_counter()
    for t in range(2):
        rt.ingest(f"v{t}", toks[t, :2], labs[t, :2])
    rt.adapt(epochs=3, batch_per_tenant=2, key=jax.random.key(3))
    recs = since(t0)
    ingests = one(recs, "ingest")
    assert len(ingests) == 2 and ingests[0].attrs == {"rows": 2}
    assert [r.name for r in kids(recs, ingests[0])
            if not r.name.startswith("compile.")] == [
        "ingest.forward", "ingest.encode", "ingest.write"]
    (adapt,) = one(recs, "adapt")
    seq = [r.name for r in kids(recs, adapt) if not r.name.startswith("compile.")]
    assert seq == ["adapt.prepare"] + ["adapt.launch"] * 3 + [
        "adapt.writeback", "adapt.sync"], seq
    ch = kids(recs, adapt)
    launches = [r for r in ch if r.name == "adapt.launch"]
    assert [r.attrs["epoch"] for r in launches] == [0, 1, 2]
    prep = next(r for r in ch if r.name == "adapt.prepare")
    assert adapt.start <= prep.start and prep.end <= launches[0].start
    sync = next(r for r in ch if r.name == "adapt.sync")
    assert sync.end <= adapt.end


def test_failed_adapt_leaves_no_span_open(cfg, params):
    rt = make_runtime(cfg, params)
    with pytest.raises(KeyError):
        rt.adapt(["nobody-ingested"])
    assert SP.RECORDER._stack() == []


# -- compile marks -----------------------------------------------------------


@pytest.mark.parametrize("module", ["runtime.py", "scheduler.py",
                                    "fleet_finetune.py"])
def test_every_compiled_body_marks_its_trace(module):
    """Every jit in these modules goes through ``jit_named``, under a name
    of its own: no bare ``jax.jit`` call is left to compile unmarked."""
    tree = ast.parse(open(os.path.join(CORE, module)).read())
    bare, named = [], []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "jit" and \
                isinstance(f.value, ast.Name) and f.value.id == "jax":
            bare.append(node.lineno)
        if isinstance(f, ast.Name) and f.id == "jit_named":
            named.append(node.args[0].value)
    assert not bare, f"jax.jit at lines {bare}"
    assert named and len(named) == len(set(named))
    assert "f" not in named


def test_compile_marks_name_the_programs(cfg, params):
    from repro.core import fleet_finetune as FF
    from repro.core import scheduler as S

    t0 = time.perf_counter()
    before = dict(SP.TRACE_COUNTS)
    rt = adapted(cfg, params)
    rt.attach_scheduler(max_batch=2, max_prompt=8, max_new_cap=4, chunk=2,
                        admit_bucket=1)
    rt.enqueue_serve("u0", np.arange(3, dtype=np.int32), max_new=4)
    rt.drain()
    marks = {r.name for r in since(t0) if r.name.startswith("compile.")}
    for name in ("sched_admit", "sched_step"):
        # a fresh geometry: this test's programs traced here
        assert SP.TRACE_COUNTS[name] > before.get(name, 0), name
        assert f"compile.{name}" in marks
    fn = S._sched_step_fn(rt.cfg, rt.use_kernel, 2, 12)
    assert fn.__name__ == "sched_step"
    opt = rt.optimizer
    epoch = FF.make_fleet_cached_epoch(rt.cfg, rt.sl, opt, 2)
    assert epoch.__name__ == "fleet_cached_epoch"


def test_warm_scheduler_takes_new_lengths_and_temperatures_untraced(cfg, params):
    rt = adapted(cfg, params)
    rt.attach_scheduler(max_batch=3, max_prompt=8, max_new_cap=6, chunk=2,
                        admit_bucket=2)
    rng = np.random.default_rng(1)
    for t in ("u0", None, "u1"):             # warm-up: both programs
        rt.enqueue_serve(t, rng.integers(0, cfg.vocab_size, 4), max_new=6)
    rt.drain()
    assert SP.TRACE_COUNTS["sched_step"] and SP.TRACE_COUNTS["sched_admit"]
    traces = sum(SP.TRACE_COUNTS.values())
    t0 = time.perf_counter()
    reqs = [rt.enqueue_serve(t, rng.integers(0, cfg.vocab_size, n),
                             max_new=m, temperature=temp)
            for t, n, m, temp in (("u0", 1, 6, 0.0), ("u1", 8, 2, 0.7),
                                  (None, 6, 5, 1.3), ("u0", 3, 1, 0.2))]
    rt.drain()
    assert all(r.done and len(r.result()) == r.max_new for r in reqs)
    assert sum(SP.TRACE_COUNTS.values()) == traces
    assert not [r for r in since(t0) if r.name.startswith("compile.")]


# -- the profiler's copy -----------------------------------------------------


def test_profiler_trace_holds_tick_spans_beside_device_events(cfg, params,
                                                              tmp_path):
    from jax.profiler import ProfileData

    rt = adapted(cfg, params)
    rt.attach_scheduler(max_batch=2, max_prompt=8, max_new_cap=4, chunk=2,
                        admit_bucket=2)
    warm = rt.enqueue_serve("u0", np.arange(4, dtype=np.int32), max_new=3)
    rt.drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        req = rt.enqueue_serve("u1", np.arange(5, dtype=np.int32), max_new=3)
        rt.drain()
    finally:
        jax.profiler.stop_trace()
    assert warm.done and req.done
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    pd = ProfileData.from_file(path)
    host, device = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SP.PREFIX):
                    host.append((e.name, e.start_ns, e.duration_ns,
                                 dict(e.stats)))
                elif line.name != "python":
                    device.append((e.start_ns, e.duration_ns))
    names = {n for n, *_ in host}
    assert {"repro.sched.step", "repro.sched.plan", "repro.sched.launch",
            "repro.sched.wait", "repro.sched.harvest"} <= names
    steps = [(a, d, st) for n, a, d, st in host if n == "repro.sched.step"]
    assert any(str(req.rid) in str(st.get("rids")) for _, _, st in steps)
    launch = next(st for n, _, _, st in host if n == "repro.sched.launch")
    assert launch["kind"] == "admit" and "rids" in launch
    # The device's work runs inside the ticks, on the same clock.
    assert device
    assert any(a <= s < a + d for a, d, _ in steps for s, _ in device)
