"""Mesh-native SessionRuntime: logical shards, placement, supervision,
elastic restore (DESIGN.md §10).

Quick tier: the whole sharding machinery runs on ONE device with a multi-
shard *logical* layout — placement, per-shard grouping, routed serve,
checkpoint round-trips, and the SessionSupervisor's zero-replay restart
are all exercised (and bitwise-compared) without forced host devices.
Nightly/full tier: subprocess runs under a forced multi-device count — the
zero-tolerance N-device/1-device twin parity and the elastic N->M restore.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_config
from repro.core import lm_skiplora as SL
from repro.core.adapter_pool import ShardedAdapterPool
from repro.core.runtime import SessionRuntime
from repro.models.lm import init_lm


@pytest.fixture(scope="module")
def cfg():
    return reduce_config(get_config("stablelm-1.6b"))


@pytest.fixture(scope="module")
def params(cfg):
    return init_lm(jax.random.key(0), cfg)


def make_sl(**kw):
    kw.setdefault("rank", 4)
    kw.setdefault("mode", "full")
    kw.setdefault("cache_dtype", "float32")
    return SL.SkipLoRAConfig(**kw)


def make_runtime(cfg, params, *, n_t=2, n_per=4, seq=8, shards=1, **kw):
    return SessionRuntime(
        cfg, make_sl(), params, max_tenants=n_t, samples_per_tenant=n_per,
        seq=seq, lr=1e-2, placement_shards=shards, **kw,
    )


def make_data(cfg, n_t, n_per, seq, seed=1):
    tokens = jax.random.randint(
        jax.random.key(seed), (n_t, n_per, seq), 0, cfg.vocab_size
    )
    labels = jax.random.randint(
        jax.random.key(seed + 1), (n_t, n_per, seq), 0, cfg.vocab_size
    )
    return tokens, labels


def run_session(rt, tokens, labels, prompts, *, rounds=1, bpt=2, epochs=1):
    n_t = tokens.shape[0]
    per_round = tokens.shape[1] // rounds
    outs, toks = [], None
    rt.serve([None] * prompts.shape[0], prompts, max_new=3)
    for rnd in range(rounds):
        lo = rnd * per_round
        for t in range(n_t):
            rt.ingest(f"u{t}", tokens[t, lo:lo + per_round],
                      labels[t, lo:lo + per_round])
        outs.append(rt.adapt(epochs=epochs, batch_per_tenant=bpt,
                             key=jax.random.key(3)))
        toks = rt.serve([f"u{t}" for t in range(n_t)][: prompts.shape[0]],
                        prompts, max_new=3)
    return outs, np.asarray(toks)


class TestLogicalShards:
    """Multi-shard layout on one device: the sharding machinery minus the
    physical placement (which tests bitwise-free separately, below)."""

    def test_multi_shard_adapters_bitwise_vs_single(self, cfg, params):
        """Splitting the session into logical shards regroups adapt
        dispatches per shard — adapters (the gradients' fixed point) must
        not move at all. (Loss *scalars* reduce over different batch
        shapes across groupings and may wobble 1 ulp; the zero-tolerance
        loss bar lives with the same-layout twin comparisons.)"""
        tokens, labels = make_data(cfg, 2, 4, 8)
        prompts = jax.random.randint(jax.random.key(5), (2, 6), 0, cfg.vocab_size)
        rt1 = make_runtime(cfg, params, shards=1)
        rt2 = make_runtime(cfg, params, shards=2)
        _, toks1 = run_session(rt1, tokens, labels, prompts)
        out2, toks2 = run_session(rt2, tokens, labels, prompts)
        assert [len(g) for g in out2[0]["groups"]] == [1, 1]
        for t in range(2):
            n = f"u{t}"
            np.testing.assert_array_equal(
                np.asarray(rt1.tenant(n).adapters["A"]),
                np.asarray(rt2.tenant(n).adapters["A"]),
            )
            np.testing.assert_array_equal(
                np.asarray(rt1.tenant(n).adapters["B"]),
                np.asarray(rt2.tenant(n).adapters["B"]),
            )
        np.testing.assert_array_equal(toks1, toks2)

    def test_partition_and_slot_placement_round_robin(self, cfg, params):
        rt = make_runtime(cfg, params, n_t=4, shards=2)
        tokens, labels = make_data(cfg, 4, 4, 8)
        for t in range(4):
            rt.ingest(f"u{t}", tokens[t], labels[t])
        # Tenant t -> shard t % 2, partition t (smallest free on its shard).
        for t in range(4):
            st = rt.tenant(f"u{t}")
            assert st.partition == t
            assert rt.pool.shard_of(f"u{t}") == t % 2
        out = rt.adapt(epochs=1, batch_per_tenant=2, key=jax.random.key(3))
        assert sorted(len(g) for g in out["groups"]) == [2, 2]
        # Same-shard tenants grouped together, not interleaved.
        assert ["u0", "u2"] in out["groups"] and ["u1", "u3"] in out["groups"]

    def test_sharded_checkpoint_roundtrip_continue(self, cfg, params, tmp_path):
        from repro.checkpoint.checkpoint import (
            restore_runtime_session,
            save_runtime_session,
        )

        tokens, labels = make_data(cfg, 2, 4, 8)
        prompts = jax.random.randint(jax.random.key(9), (2, 6), 0, cfg.vocab_size)

        def start():
            rt = make_runtime(cfg, params, shards=2)
            run_session(rt, tokens, labels, prompts)
            return rt

        rt_ref = start()
        path = save_runtime_session(str(tmp_path), 1, start())
        rt_new = make_runtime(cfg, params, shards=2)
        restore_runtime_session(path, rt_new)
        assert rt_new.pool.slot_table() == rt_ref.pool.slot_table()
        out_ref = rt_ref.adapt(epochs=1, batch_per_tenant=2)
        out_new = rt_new.adapt(epochs=1, batch_per_tenant=2)
        for t in range(2):
            n = f"u{t}"
            np.testing.assert_array_equal(out_ref["losses"][n],
                                          out_new["losses"][n])
            np.testing.assert_array_equal(
                np.asarray(rt_ref.tenant(n).adapters["B"]),
                np.asarray(rt_new.tenant(n).adapters["B"]),
            )
        np.testing.assert_array_equal(
            np.asarray(rt_ref.serve(["u0", "u1"], prompts, max_new=3)),
            np.asarray(rt_new.serve(["u0", "u1"], prompts, max_new=3)),
        )

    def test_restore_rejects_shard_count_mismatch(self, cfg, params, tmp_path):
        """The logical shard count is a session-LAYOUT property: elastic
        restarts change devices, never shards."""
        from repro.checkpoint.checkpoint import (
            restore_runtime_session,
            save_runtime_session,
        )

        rt = make_runtime(cfg, params, shards=2)
        tokens, labels = make_data(cfg, 1, 4, 8)
        rt.ingest("u0", tokens[0], labels[0])
        path = save_runtime_session(str(tmp_path), 0, rt)
        with pytest.raises(ValueError, match="layout|shard"):
            restore_runtime_session(path, make_runtime(cfg, params, shards=1))

    def test_session_full_per_shard(self, cfg, params):
        rt = make_runtime(cfg, params, n_t=2, shards=2)
        tokens, labels = make_data(cfg, 3, 4, 8)
        rt.ingest("u0", tokens[0], labels[0])
        rt.ingest("u1", tokens[1], labels[1])
        with pytest.raises(RuntimeError, match="session full"):
            rt.ingest("u2", tokens[2], labels[2])
        rt.release("u0")
        rt.ingest("u2", tokens[2], labels[2])  # shard 0's partition recycled
        assert rt.pool.shard_of("u2") == 0


class TestShardedPool:
    def test_placement_balanced_and_sticky(self, cfg):
        pool = ShardedAdapterPool(3, cfg, 4, n_shards=3)
        assert [pool.place(f"t{i}") for i in range(6)] == [0, 1, 2, 0, 1, 2]
        assert pool.place("t4") == 1  # sticky
        pool.unplace("t4")
        # t4 gone: shard 1 now has the fewest placed tenants.
        assert pool.place("fresh") == 1

    def test_route_and_register_many_mixed_shards(self, cfg):
        sl = make_sl()
        pool = ShardedAdapterPool(3, cfg, sl.rank, n_shards=2)
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[SL.init_adapters(jax.random.key(i), cfg, sl) for i in range(4)],
        )
        tenants = [f"t{i}" for i in range(4)]
        pool.register_many(tenants, stacked)
        for i, t in enumerate(tenants):
            s = pool.shard_of(t)
            assert s == i % 2
            idx = int(pool.lookup_local(s, [t])[0])
            np.testing.assert_array_equal(
                np.asarray(pool.shard_pools(s)["A"][idx]),
                np.asarray(stacked["A"][i]),
            )
        routed = pool.route([None, "t3", "t0", "t2"])
        assert routed[0] == ([0, 2, 3], [None, "t0", "t2"])
        assert routed[1] == ([1], ["t3"])

    def test_single_shard_delegates_plain_pool_surface(self, cfg):
        sl = make_sl()
        pool = ShardedAdapterPool(3, cfg, sl.rank, n_shards=1)
        ad = SL.init_adapters(jax.random.key(0), cfg, sl)
        pool.register("t0", ad)
        assert pool.has("t0") and len(pool) == 1
        assert int(pool.lookup(["t0"])[0]) == 1
        assert set(pool.pools()) == {"A", "B"}
        with pytest.raises(RuntimeError, match="multi-shard"):
            ShardedAdapterPool(3, cfg, sl.rank, n_shards=2).pools()


class TestBatchPlanStreams:
    def test_streams_decouple_rng_from_partition_offset(self):
        from repro.core import batch_plan

        ref = batch_plan.fleet_index_matrix(
            2, 2, 8, 4, seed=0, partitions=[1, 3], partition_stride=8
        )
        # Same RNG streams (global partitions 1, 3) but shard-local offsets
        # (local partitions 0, 1): identical visitation orders, shifted.
        loc = batch_plan.fleet_index_matrix(
            2, 2, 8, 4, seed=0, partitions=[0, 1], streams=[1, 3],
            partition_stride=8,
        )
        np.testing.assert_array_equal(ref[:, :4] - 8, loc[:, :4])
        np.testing.assert_array_equal(ref[:, 4:] - 16, loc[:, 4:])

    def test_streams_length_mismatch_raises(self):
        from repro.core import batch_plan

        with pytest.raises(ValueError, match="streams"):
            batch_plan.fleet_index_matrix(0, 2, 4, 2, streams=[0])


class TestSupervisor:
    def test_zero_replay_restart_reproduces_uninterrupted_run(
        self, cfg, params, tmp_path
    ):
        """A SessionSupervisor crash drill: every completed event executes
        exactly once across incarnations, the failed event exactly twice
        (its first attempt's partial state is discarded with the runtime),
        and the final adapters equal the uninterrupted run's bitwise."""
        from repro.runtime import SessionSupervisor

        tokens, labels = make_data(cfg, 2, 4, 8)
        prompts = jax.random.randint(jax.random.key(5), (2, 6), 0, cfg.vocab_size)
        counts = [0] * 4
        fail_once = {"armed": True}

        def make_events(counting: bool):
            def ingest(t):
                def run(rt, i):
                    if counting:
                        counts[i] += 1
                    return rt.ingest(f"u{t}", tokens[t], labels[t])
                return run

            def adapt(rt, i):
                if counting:
                    counts[i] += 1
                if counting and fail_once["armed"]:
                    fail_once["armed"] = False
                    raise RuntimeError("injected mid-adapt failure")
                return rt.adapt(epochs=1, batch_per_tenant=2,
                                key=jax.random.key(3))

            def serve(rt, i):
                if counting:
                    counts[i] += 1
                return rt.serve(["u0", "u1"], prompts, max_new=3)

            return [ingest(0), ingest(1), adapt, serve]

        # Uninterrupted reference (no supervisor, same events).
        rt_ref = make_runtime(cfg, params, shards=2)
        for i, ev in enumerate(make_events(counting=False)):
            ev(rt_ref, i)

        sup = SessionSupervisor(str(tmp_path / "ckpt"), save_every=1)
        rt, info = sup.run(
            lambda: make_runtime(cfg, params, shards=2),
            make_events(counting=True),
        )
        assert info["restarts"] == 1
        assert info["resumed_at"] == 2  # rolled back to the adapt boundary
        assert counts == [1, 1, 2, 1]   # zero replay; only the crash retries
        for t in range(2):
            n = f"u{t}"
            np.testing.assert_array_equal(
                np.asarray(rt.tenant(n).adapters["B"]),
                np.asarray(rt_ref.tenant(n).adapters["B"]),
            )
        assert rt.pool.slot_table() == rt_ref.pool.slot_table()

    def test_supervisor_gives_up_past_max_restarts(self, cfg, params, tmp_path):
        from repro.runtime import SessionSupervisor

        sup = SessionSupervisor(str(tmp_path / "ckpt"), max_restarts=1)

        def always_fails(rt, i):
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            sup.run(lambda: make_runtime(cfg, params), [always_fails])


class TestRuntimePublicAPI:
    def test_one_import_path(self):
        import repro.runtime as R

        for name in ("AxisRules", "Supervisor", "SessionSupervisor",
                     "StragglerMonitor", "elastic_remesh",
                     "elastic_session_mesh", "make_mesh", "session_devices",
                     "session_param_specs", "replicate_backbone",
                     "SessionRuntime",
                     # the 2-D session surface (DESIGN.md §14)
                     "session_mesh_layout", "shard_submesh", "shard_backbone",
                     "ShardScope", "scope_ctx", "SESSION_TP_RULES",
                     "per_device_bytes",
                     # pipeline parallelism
                     "split_stages", "pipeline_apply", "pipeline_prefill",
                     "bubble_fraction"):
            assert getattr(R, name) is not None
            assert name in dir(R)
        with pytest.raises(AttributeError):
            R.not_a_thing

    def test_make_mesh_validates(self):
        from repro.runtime import make_mesh

        with pytest.raises(ValueError, match="axes"):
            make_mesh((1, 1), ("data",))
        with pytest.raises(ValueError, match="devices"):
            make_mesh((2,), ("data",), devices=jax.devices()[:1])
        mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
        assert mesh.axis_names == ("data",)


# ---------------------------------------------------------------------------
# --check-parity's bar on host snapshots
# ---------------------------------------------------------------------------


def _snapshot(seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "adapters": {
            "t0": {"A": rng.normal(size=(2, 8, 4)).astype(np.float32),
                   "B": rng.normal(size=(2, 4, 8)).astype(np.float32)},
        },
        "losses": {"adapt/r0": {"t0": rng.uniform(5, 7, size=(2, 3)).astype(np.float32)}},
        "tokens": {"serve/base": rng.integers(0, 503, size=(3, 4))},
        "slots": {"t0": 1},
    }


def _perturb(snap: dict, what: str, eps: float) -> dict:
    twin = {k: v for k, v in snap.items()}
    if what == "adapters":
        twin["adapters"] = {"t0": {**snap["adapters"]["t0"],
                                   "B": snap["adapters"]["t0"]["B"] + eps}}
    elif what == "losses":
        twin["losses"] = {"adapt/r0": {"t0": snap["losses"]["adapt/r0"]["t0"] * (1 + eps)}}
    elif what == "tokens":
        toks = snap["tokens"]["serve/base"].copy()
        toks[1, 2] = (toks[1, 2] + 1) % 503
        twin["tokens"] = {"serve/base": toks}
    elif what == "slots":
        twin["slots"] = {"t0": 2}
    return twin


class TestParityBar:
    """``run.parity_diffs``: bitwise on a data mesh; on a model axis,
    tokens and slot tables exact, adapters/losses within rtol 1e-3 / atol
    1e-5 — a float32 reduction-order difference passes, a fault does not."""

    @pytest.mark.parametrize("n_model,what,eps,expect", [
        (1, None, 0.0, []),
        (1, "adapters", 1e-6, ["adapters[t0][B]"]),
        (2, None, 0.0, []),
        (2, "adapters", 3e-6, []),
        (2, "losses", 1e-5, []),
        (2, "adapters", 1e-2, ["adapters[t0][B]"]),
        (2, "losses", 1e-2, ["losses[adapt/r0][t0]"]),
        (2, "tokens", 0.0, ["tokens[serve/base]"]),
        (2, "slots", 0.0, ["pool slot tables"]),
    ])
    def test_bar(self, n_model, what, eps, expect):
        from types import SimpleNamespace

        from repro.launch import run as RUN

        session = SimpleNamespace(names=["t0"], n_model=n_model)
        snap = _snapshot()
        twin = _perturb(snap, what, eps) if what else _snapshot()
        diffs, measured = RUN.parity_diffs(session, snap, twin)
        assert diffs == expect
        assert measured["token_agreement"] == (
            1.0 - 1 / 12 if what == "tokens" else 1.0
        )
        assert measured["adapter_elements_outside_bar"] == (
            64.0 if expect == ["adapters[t0][B]"] else 0.0
        )


# ---------------------------------------------------------------------------
# Forced multi-device tier (subprocess; nightly/full)
# ---------------------------------------------------------------------------


def _forced_env(n: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + env.get("XLA_FLAGS", "")
    )
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
class TestForcedMultiDevice:
    def test_run_cli_twin_parity_zero_tolerance(self):
        """launch/run.py --devices 2 --check-parity: the sharded session
        must equal its 1-device same-layout twin at ZERO tolerance."""
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.run",
             "--tenants", "2", "--devices", "2", "--rounds", "1",
             "--samples-per-round", "4", "--seq", "8", "--gen", "4",
             "--adapt-epochs", "2", "--check-parity"],
            capture_output=True, text=True, timeout=600, env=_forced_env(2),
            cwd=_repo_root(),
        )
        assert out.returncode == 0, out.stderr[-2000:] + out.stdout[-2000:]
        assert "parity OK" in out.stdout

    def test_elastic_restore_different_device_count(self, tmp_path):
        """Save a sharded session on N forced devices, restore and continue
        on M != N: adapter/loss parity with the uninterrupted run (the
        logical layout travels in the checkpoint; only placement changes,
        and placement is bitwise-free)."""
        script = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, numpy as np
from repro.configs import get_config, reduce_config
from repro.core import lm_skiplora as SL
from repro.core.runtime import SessionRuntime
from repro.checkpoint.checkpoint import restore_runtime_session, save_runtime_session
from repro.models.lm import init_lm
from repro.runtime.sharding import make_mesh

ckdir = sys.argv[1]
cfg = reduce_config(get_config("stablelm-1.6b"))
sl = SL.SkipLoRAConfig(rank=4, mode="full", cache_dtype="float32")
params = init_lm(jax.random.key(0), cfg)
n_t, n_per, seq, bpt = 4, 4, 8, 2
tokens = jax.random.randint(jax.random.key(1), (n_t, n_per, seq), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.key(2), (n_t, n_per, seq), 0, cfg.vocab_size)
prompts = jax.random.randint(jax.random.key(5), (n_t, 6), 0, cfg.vocab_size)

def fresh(n_devices):
    mesh = make_mesh((n_devices,), ("data",), devices=jax.devices()[:n_devices])
    return SessionRuntime(cfg, sl, params, max_tenants=n_t,
                          samples_per_tenant=n_per, seq=seq, lr=1e-2,
                          mesh=mesh, placement_shards=2)

def first_half(rt):
    for t in range(n_t):
        rt.ingest(f"u{t}", tokens[t, :2], labels[t, :2])
    return rt.adapt(epochs=1, batch_per_tenant=bpt, key=jax.random.key(3))

def second_half(rt):
    for t in range(n_t):
        rt.ingest(f"u{t}", tokens[t, 2:], labels[t, 2:])
    out = rt.adapt(epochs=2, batch_per_tenant=bpt)
    toks = rt.serve([f"u{t}" for t in range(n_t)], prompts, max_new=3)
    return out, np.asarray(toks)

# Uninterrupted run: 2 shards on 2 devices, end to end.
rt_ref = fresh(2)
first_half(rt_ref)
out_ref, toks_ref = second_half(rt_ref)

# Interrupted run: same start, checkpoint, restore onto 4 devices (M != N).
rt_a = fresh(2)
first_half(rt_a)
path = save_runtime_session(ckdir, 1, rt_a)
rt_b = fresh(4)
restore_runtime_session(path, rt_b)
out_b, toks_b = second_half(rt_b)

for t in range(n_t):
    n = f"u{t}"
    np.testing.assert_array_equal(out_ref["losses"][n], out_b["losses"][n])
    np.testing.assert_array_equal(np.asarray(rt_ref.tenant(n).adapters["A"]),
                                  np.asarray(rt_b.tenant(n).adapters["A"]))
    np.testing.assert_array_equal(np.asarray(rt_ref.tenant(n).adapters["B"]),
                                  np.asarray(rt_b.tenant(n).adapters["B"]))
np.testing.assert_array_equal(toks_ref, toks_b)
assert rt_ref.pool.slot_table() == rt_b.pool.slot_table()
devs = {str(next(iter(st.adapters["A"].devices()))) for st in rt_b._tenants.values()}
assert len(devs) == 2, devs  # 2 logical shards -> 2 of the 4 devices
print("ELASTIC_RESTORE_PARITY_OK")
"""
        out = subprocess.run(
            [sys.executable, "-c", script, str(tmp_path / "ck")],
            capture_output=True, text=True, timeout=600, env=_forced_env(4),
            cwd=_repo_root(),
        )
        assert out.returncode == 0, out.stderr[-3000:]
        assert "ELASTIC_RESTORE_PARITY_OK" in out.stdout

    def test_mesh_2d_twin_parity_and_elastic_restore(self, tmp_path):
        """(data=2, model=2) forced mesh vs the 1-device same-layout twin:
        serve TOKENS (temp-0) exact — including through the pipelined
        scheduler admission — adapters within TP float tolerance (the model
        axis reorders partial sums), slot tables equal, per-device backbone
        bytes ~halved; then a checkpoint from the 2-D session restores into
        the 1-device twin and both continue in lockstep (the mesh shape is
        placement, not layout — DESIGN.md §14)."""
        script = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
from repro.models.config import ModelConfig
from repro.models.lm import init_lm
from repro.core.lm_skiplora import SkipLoRAConfig
from repro.core.runtime import SessionRuntime
from repro.checkpoint.checkpoint import restore_runtime_session, save_runtime_session
from repro.runtime.sharding import make_mesh

ckdir = sys.argv[1]
cfg = ModelConfig(name="t", family="test", n_layers=4, d_model=16, n_heads=4,
                  n_kv_heads=2, d_ff=32, vocab_size=64, pattern=("attn",),
                  dtype="float32")
sl = SkipLoRAConfig(rank=2, mode="full")
params = init_lm(jax.random.key(0), cfg)

def build(mesh=None, pipeline_stages=0):
    return SessionRuntime(cfg, sl, params, max_tenants=4, samples_per_tenant=8,
                          seq=6, use_kernel=False, mesh=mesh,
                          placement_shards=2, seed=0,
                          pipeline_stages=pipeline_stages)

mesh2 = make_mesh((2, 2), ("data", "model"), devices=jax.devices())
rt1, rt2, rtp = build(), build(mesh2), build(mesh2, pipeline_stages=2)
assert rt2.model_parallel == 2 and rt2.n_shards == 2
prompts = jax.random.randint(jax.random.key(4), (2, 5), 0, cfg.vocab_size)
tokens = jax.random.randint(jax.random.key(5), (2, 6), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.key(6), (2, 6), 0, cfg.vocab_size)
for rt in (rt1, rt2, rtp):
    for t in ("a", "b", "c"):
        rt.ingest(t, tokens, labels)
    rt.adapt(["a", "b", "c"], epochs=2, key=jax.random.key(7))

def adapters_close(x, y):
    for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)

for t in ("a", "b", "c"):
    adapters_close(rt1.tenant(t).adapters, rt2.tenant(t).adapters)
assert rt1.pool.slot_table() == rt2.pool.slot_table()
np.testing.assert_array_equal(
    np.asarray(rt1.serve([None, "a"], prompts, max_new=4)),
    np.asarray(rt2.serve([None, "a"], prompts, max_new=4)))

# One backbone replica per data group, TP-split over its 2 model devices.
total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(params))
per = max(sum(s.data.nbytes for x in jax.tree.leaves(rt2._shard_params[0])
              for s in x.addressable_shards if s.device == d)
          for d in rt2.mesh.devices.ravel())
assert total / per > 1.5, (total, per)

# Pipelined admission: tokens exact vs plain 2-D and vs 1 device.
outs = []
for rt in (rt1, rt2, rtp):
    rt.attach_scheduler(max_batch=4, max_prompt=5, max_new_cap=8,
                        admit_bucket=2, chunk=2)
    reqs = [rt.enqueue_serve("a", prompts[0, :4], max_new=6),
            rt.enqueue_serve(None, prompts[1, :3], max_new=5)]
    rt.drain()
    outs.append([r.result().tolist() for r in reqs])
assert outs[0] == outs[1] == outs[2], outs
assert abs(rtp.scheduler.predicted_bubble() - 1/3) < 1e-12

# Elastic restore ACROSS mesh shapes: checkpoint the (2,2) session, restore
# into the 1-device twin, continue both with the same events.
path = save_runtime_session(ckdir, 1, rt2)
rt_back = build()
restore_runtime_session(path, rt_back)
for rt in (rt2, rt_back):
    for t in ("a", "b", "c"):
        rt.ingest(t, labels, tokens)
    rt.adapt(["a", "b", "c"], epochs=1, key=jax.random.key(8))
for t in ("a", "b", "c"):
    adapters_close(rt2.tenant(t).adapters, rt_back.tenant(t).adapters)
assert rt2.pool.slot_table() == rt_back.pool.slot_table()
np.testing.assert_array_equal(
    np.asarray(rt2.serve([None, "b"], prompts, max_new=4)),
    np.asarray(rt_back.serve([None, "b"], prompts, max_new=4)))
print("MESH2D_PARITY_OK")
"""
        out = subprocess.run(
            [sys.executable, "-c", script, str(tmp_path / "ck")],
            capture_output=True, text=True, timeout=600, env=_forced_env(4),
            cwd=_repo_root(),
        )
        assert out.returncode == 0, out.stderr[-3000:]
        assert "MESH2D_PARITY_OK" in out.stdout

    def test_run_cli_mesh_2d_pipelined(self):
        """launch/run.py --mesh 2x2 --pipeline-stages 2 --scheduler
        --check-parity: tokens exact, adapters within TP tolerance."""
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.run",
             "--mesh", "2x2", "--pipeline-stages", "2", "--scheduler",
             "--tenants", "2", "--rounds", "1", "--samples-per-round", "4",
             "--seq", "8", "--prompt-len", "5", "--gen", "4",
             "--check-parity"],
            capture_output=True, text=True, timeout=600, env=_forced_env(4),
            cwd=_repo_root(),
        )
        assert out.returncode == 0, out.stderr[-2000:] + out.stdout[-2000:]
        assert "parity OK" in out.stdout

    def test_supervised_elastic_failure_cli(self, tmp_path):
        """launch/run.py crash drill: injected failure mid-stream, restart
        on fewer devices, session completes."""
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.run",
             "--tenants", "2", "--devices", "2", "--rounds", "2",
             "--samples-per-round", "2", "--seq", "8", "--gen", "4",
             "--adapt-epochs", "1",
             "--checkpoint-dir", str(tmp_path / "ck"),
             "--inject-failure", "3", "--elastic-devices", "1"],
            capture_output=True, text=True, timeout=600, env=_forced_env(2),
            cwd=_repo_root(),
        )
        assert out.returncode == 0, out.stderr[-2000:] + out.stdout[-2000:]
        assert "1 restarts" in out.stdout
