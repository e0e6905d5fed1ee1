"""Pipeline-parallelism tests.

The GPipe schedule needs >1 device for a real pipeline; pytest runs with the
single CPU device, so the multi-device check runs in a subprocess with
forced host devices. The in-process tests cover the schedule math and stage
splitting.
"""

import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.pipeline_par import bubble_fraction, split_stages


class TestScheduleMath:
    def test_bubble_fraction(self):
        assert bubble_fraction(1, 1) == 0.0
        assert bubble_fraction(4, 2) == 1 / 5
        assert bubble_fraction(16, 4) < 0.2

    def test_split_stages_shapes(self):
        layers = [{"w": jnp.full((3,), i, jnp.float32)} for i in range(8)]
        st, valid = split_stages(layers, 4)
        assert st["w"].shape == (4, 2, 3)
        np.testing.assert_array_equal(np.asarray(st["w"][1, 0]), np.full(3, 2.0))
        assert valid.shape == (4, 2) and bool(jnp.all(valid))

    def test_split_stages_remainder_pads_invalid(self):
        # 6 layers over 4 stages: ceil division gives 2 slots per stage;
        # the last stage's slots are copies of the final layer, marked
        # invalid so pipeline runners pass through them unchanged.
        layers = [{"w": jnp.full((2,), i, jnp.float32)} for i in range(6)]
        st, valid = split_stages(layers, 4)
        assert st["w"].shape == (4, 2, 2)
        np.testing.assert_array_equal(
            np.asarray(valid),
            np.array([[1, 1], [1, 1], [1, 1], [0, 0]], bool),
        )
        np.testing.assert_array_equal(np.asarray(st["w"][3, 1]), np.full(2, 5.0))

    def test_split_stages_errors(self):
        with pytest.raises(ValueError):
            split_stages([], 2)
        with pytest.raises(ValueError):
            split_stages([{"w": jnp.zeros(2)} for _ in range(3)], 4)


SUBPROCESS_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.runtime.pipeline_par import pipeline_apply, split_stages
    from repro.runtime.sharding import make_mesh

    mesh = make_mesh((4,), ("pod",))
    L, D = 8, 16
    key = jax.random.key(0)
    layers = [
        {"w": jax.random.normal(jax.random.key(i), (D, D)) / np.sqrt(D)}
        for i in range(L)
    ]

    def layer_fn(p, h):
        return jnp.tanh(h @ p["w"])

    stages, valid = split_stages(layers, 4)
    x = jax.random.normal(key, (6, 4, D))  # 6 microbatches of 4

    out = pipeline_apply(stages, x, layer_fn, mesh=mesh, axis="pod", valid=valid)

    # Reference: plain sequential stack.
    ref = x
    for p in layers:
        ref = layer_fn(p, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    # Remainder split: 6 layers over 4 stages — the padded slots must pass
    # activations through unchanged.
    stages6, valid6 = split_stages(layers[:6], 4)
    out6 = pipeline_apply(stages6, x, layer_fn, mesh=mesh, axis="pod", valid=valid6)
    ref6 = x
    for p in layers[:6]:
        ref6 = layer_fn(p, ref6)
    np.testing.assert_allclose(np.asarray(out6), np.asarray(ref6), atol=1e-5)

    # Differentiability: grad through the pipeline matches the reference.
    def loss_pipe(stages):
        return jnp.sum(pipeline_apply(stages, x, layer_fn, mesh=mesh, axis="pod") ** 2)

    def loss_ref(stages):
        h = x
        flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), stages)
        for i in range(L):
            h = layer_fn(jax.tree.map(lambda a: a[i], flat), h)
        return jnp.sum(h ** 2)

    g1 = jax.grad(loss_pipe)(stages)
    g2 = jax.grad(loss_ref)(stages)
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]), atol=1e-4)
    print("PIPELINE_OK")
    """
)


@pytest.mark.slow  # forces a fresh multi-device subprocess: ~8 min alone
class TestPipelineMultiDevice:
    def test_pipeline_matches_sequential_subprocess(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        env["JAX_PLATFORMS"] = "cpu"  # forced host devices, never a chip
        res = subprocess.run(
            [sys.executable, "-c", SUBPROCESS_PROG],
            capture_output=True,
            text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=600,
        )
        assert "PIPELINE_OK" in res.stdout, res.stdout + res.stderr
