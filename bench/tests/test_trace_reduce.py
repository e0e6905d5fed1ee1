"""Trace reduction on a synthetic trace, checked by hand."""

import pytest

from bench import trace_reduce as TR

MS = 1_000_000  # ns


def test_merge_clips_and_unions():
    got = TR.merge([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 12)
    assert got == [(1, 4), (5, 8), (9, 12)]


def test_reduce_busy_ops_and_gaps():
    # Window [0, 100) ms. Device: a while loop 10-40 with a body op b
    # 20-30 nested in it, op a 60-70, op a 95-120 (cut by the window);
    # host: step 0-50, idle 50-100.
    ops = {"/device:TPU:0": [("while.3", 10 * MS, 40 * MS), ("b", 20 * MS, 30 * MS),
                             ("a", 60 * MS, 70 * MS), ("a", 95 * MS, 120 * MS)]}
    spans = [("step", 0, 50 * MS), ("idle", 50 * MS, 100 * MS),
             ("window", 0, 100 * MS)]
    r = TR.reduce(ops, spans, 0, 100 * MS)
    assert r["window_s"] == pytest.approx(0.1)
    # busy = 10-40 (30) + 60-70 (10) + 95-100 (5) = 45 ms
    assert r["busy_s"] == pytest.approx(0.045)
    # self time: the loop less its body, b, and a's 10 + 5 ms in the window
    assert r["op_s"]["while.3"] == pytest.approx(0.020)
    assert r["op_s"]["b"] == pytest.approx(0.010)
    assert r["op_s"]["a"] == pytest.approx(0.015)
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"])
    gaps = dict(r["idle_gaps"])
    # gaps: 0-10 (step), 40-60 (midpoint 50: idle starts at 50), 70-95 (idle)
    assert gaps["step"] == pytest.approx(0.010)
    assert gaps["idle"] == pytest.approx(0.045)
    assert TR.kernel_seconds(r["op_s"], "while") == pytest.approx(0.020)


def test_op_names():
    assert TR.op_name("%copy.58 = bf16[48,4608,1536] copy(%fusion.133)") == "copy.58"
    assert TR.op_name("skip_lora_grouped_fwd.14") == "skip_lora_grouped_fwd.14"
    assert TR.kernel_seconds({"skip_lora_grouped_fwd.14": 1.0,
                              "jvp_jit_skip_lora_grouped_fwd__.2": 2.0},
                             "skip_lora_grouped_fwd") == 1.0


def test_reduce_averages_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 50 * MS)],
           "/device:TPU:1": [("a", 0, 100 * MS)]}
    r = TR.reduce(ops, [], 0, 100 * MS)
    assert r["busy_s"] == pytest.approx(0.075)
    assert r["op_s"]["a"] == pytest.approx(0.075)
    assert dict(r["idle_gaps"])["none"] == pytest.approx(0.025)


def test_recorded_cpu_trace_has_window_and_spans(tmp_path):
    """A real (CPU) profiler trace: the harness's host spans come back
    from the file with their names."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, spans = TR.read_events(TR.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in spans]
    assert "window" in names and "step" in names
    r = TR.reduce_trace(str(tmp_path))
    assert r["window_s"] > 0
