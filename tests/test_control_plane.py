"""Adapter control plane (DESIGN.md §13): shadow split, regression gate,
versioned slots with rollback, and their checkpoint story.

Quick tier, all of it. The gate is strictly opt-in — a session without a
``ControlConfig`` must plan and write back bitwise as before — so these
tests cover the policy (ControlPlane), the mechanism (AdapterPool version
history + gated ``register_many``), the orchestration (SessionRuntime
reject/quarantine semantics on both the resident-scan and streaming adapt
paths), and the end-to-end poisoned-corpus acceptance bar.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_config
from repro.core import batch_plan
from repro.core import lm_skiplora as SL
from repro.core.adapter_pool import AdapterPool
from repro.core.control_plane import ControlConfig, ControlPlane
from repro.core.runtime import SessionRuntime
from repro.models.lm import init_lm

COMPRESS = [None, "int8", "int4", "nf4"]


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


def make_runtime(cfg, params, *, n_t=2, n_per=8, seq=8, control=None, **kw):
    return SessionRuntime(
        cfg, make_sl(), params, max_tenants=n_t, samples_per_tenant=n_per,
        seq=seq, lr=5e-2, control=control, **kw
    )


def make_data(cfg, n_t, n_per, seq, seed=1):
    tokens = jax.random.randint(
        jax.random.key(seed), (n_t, n_per, seq), 0, cfg.vocab_size
    )
    labels = jax.random.randint(
        jax.random.key(seed + 1), (n_t, n_per, seq), 0, cfg.vocab_size
    )
    return tokens, labels


def make_adapters(cfg, seed, rank=4):
    ad = SL.init_adapters(jax.random.key(seed), cfg, make_sl(rank=rank))
    ad["B"] = jax.random.normal(jax.random.key(seed + 100), ad["B"].shape) * 0.05
    return ad


def slot_payload_np(pool, tenant):
    return {n: np.asarray(v) for n, v in pool.slot_payload(tenant).items()}


# An always-firing gate: any finite delta exceeds -inf, so the second
# write-back of any tenant is deterministically gated without needing a
# crafted regression.
ALWAYS = ControlConfig(holdout_every=4, threshold=float("-inf"))
NEVER = ControlConfig(holdout_every=4, threshold=float("inf"))


class TestShadowSplit:
    def test_holdout_rule_and_append_stability(self):
        train, held = batch_plan.shadow_split(16, every=4)
        np.testing.assert_array_equal(held, [3, 7, 11, 15])
        np.testing.assert_array_equal(
            np.sort(np.concatenate([train, held])), np.arange(16)
        )
        # Appending rows never reassigns an existing row between sides.
        t2, h2 = batch_plan.shadow_split(23, every=4)
        np.testing.assert_array_equal(h2[: held.size], held)
        np.testing.assert_array_equal(t2[: train.size], train)
        assert 0 in train  # row 0 always trains

    def test_none_is_all_train_and_validation(self):
        train, held = batch_plan.shadow_split(5, every=None)
        np.testing.assert_array_equal(train, np.arange(5))
        assert held.size == 0
        with pytest.raises(ValueError, match="every"):
            batch_plan.shadow_split(5, every=1)

    def test_fleet_index_matrix_trains_complement_only(self):
        idx = batch_plan.fleet_index_matrix(
            epoch=0, n_tenants=2, samples_per_tenant=8, batch_per_tenant=2,
            holdout_every=4,
        )
        train, held = batch_plan.shadow_split(8, every=4)
        for g in range(2):
            block = idx[:, g * 2:(g + 1) * 2].ravel() - g * 8
            assert sorted(block.tolist()) == sorted(train.tolist())
            assert not set(block.tolist()) & set(held.tolist())

    def test_holdout_none_is_bitwise_historical(self):
        a = batch_plan.fleet_index_matrix(
            epoch=3, n_tenants=2, samples_per_tenant=8, batch_per_tenant=4
        )
        b = batch_plan.fleet_index_matrix(
            epoch=3, n_tenants=2, samples_per_tenant=8, batch_per_tenant=4,
            holdout_every=None,
        )
        np.testing.assert_array_equal(a, b)

    def test_fleet_eval_index_layout(self):
        idx = batch_plan.fleet_eval_index(
            2, 8, holdout_every=4, partitions=[2, 0], partition_stride=16
        )
        np.testing.assert_array_equal(idx, [2 * 16 + 3, 2 * 16 + 7, 3, 7])
        with pytest.raises(ValueError, match="no held-out"):
            batch_plan.fleet_eval_index(1, 3, holdout_every=4)


class TestControlPolicy:
    def test_config_validation(self):
        with pytest.raises(ValueError, match="holdout_every"):
            ControlConfig(holdout_every=1)
        with pytest.raises(ValueError, match="mode"):
            ControlConfig(mode="warn")
        with pytest.raises(ValueError, match="history_depth"):
            ControlConfig(history_depth=0)

    def test_decide_semantics(self):
        cp = ControlPlane(ControlConfig(threshold=0.1, mode="quarantine"))
        assert cp.decide("t", None, 3.0) == "accept"   # no eval rows
        assert cp.decide("t", 3.0, None) == "accept"
        assert cp.decide("t", 3.0, 3.05) == "accept"   # within threshold
        assert cp.decide("t", 3.0, 3.2) == "quarantine"
        assert ControlPlane(ControlConfig()).decide("t", 3.0, 3.2) == "reject"

    def test_ledger_and_quarantine_lifecycle(self):
        cp = ControlPlane(ControlConfig(mode="quarantine"))
        cp.record(7, "quarantine", pre=1.0, post=2.0, step=4)
        assert cp.is_quarantined(7) and cp.quarantined == 1
        assert cp.last(7)["delta"] == 1.0
        cp.record(7, "accept", pre=2.0, post=1.5, step=8)
        assert not cp.is_quarantined(7) and cp.accepted == 1
        cp.record(7, "quarantine", pre=1.5, post=9.0, step=12)
        cp.record_rollback(7)
        assert not cp.is_quarantined(7) and cp.rollbacks == 1
        assert cp.last(7) is None
        with pytest.raises(ValueError, match="decision"):
            cp.record(7, "maybe")

    def test_auto_rollback_streak_policy(self):
        with pytest.raises(ValueError, match="auto_rollback_after"):
            ControlConfig(auto_rollback_after=0)
        cp = ControlPlane(ControlConfig(auto_rollback_after=2))
        cp.record("t", "reject", pre=1.0, post=2.0, step=1)
        assert not cp.should_auto_rollback("t")
        cp.record("t", "reject", pre=1.0, post=2.0, step=2)
        assert cp.should_auto_rollback("t")
        cp.record_rollback("t", auto=True)
        assert (cp.rollbacks, cp.auto_rollbacks) == (1, 1)
        assert not cp.should_auto_rollback("t")        # streak cleared
        # An accept resets the streak mid-way.
        cp.record("t", "reject", pre=1.0, post=2.0, step=3)
        cp.record("t", "accept", pre=1.0, post=0.5, step=4)
        cp.record("t", "reject", pre=0.5, post=2.0, step=5)
        assert not cp.should_auto_rollback("t")
        # Manual rollbacks don't count as auto.
        cp.record_rollback("t")
        assert (cp.rollbacks, cp.auto_rollbacks) == (2, 1)
        # Disabled (the default): streaks accumulate but never fire.
        cp0 = ControlPlane(ControlConfig())
        cp0.record("t", "reject", pre=1.0, post=2.0, step=1)
        cp0.record("t", "reject", pre=1.0, post=2.0, step=2)
        assert not cp0.should_auto_rollback("t")

    def test_streaks_survive_state_roundtrip(self):
        cp = ControlPlane(ControlConfig(mode="quarantine", auto_rollback_after=3))
        cp.record(3, "quarantine", pre=1.0, post=2.0, step=1)
        cp.record(3, "quarantine", pre=1.0, post=2.0, step=2)
        wire = json.loads(json.dumps(cp.state()))
        cp2 = ControlPlane(cp.config)
        cp2.load_state(wire)
        assert not cp2.should_auto_rollback(3)
        cp2.record(3, "quarantine", pre=1.0, post=2.0, step=3)
        assert cp2.should_auto_rollback(3)             # int key survived JSON

    def test_state_roundtrips_int_tenants_through_json(self):
        cp = ControlPlane(ControlConfig(mode="quarantine"))
        cp.record(3, "reject", pre=1.0, post=2.0, step=2)
        cp.record(4, "quarantine", pre=1.0, post=2.0, step=2)
        wire = json.loads(json.dumps(cp.state()))
        cp2 = ControlPlane(cp.config)
        cp2.load_state(wire)
        assert cp2.last(3)["decision"] == "reject"    # int key survived
        assert cp2.is_quarantined(4)
        assert (cp2.accepted, cp2.rejected, cp2.quarantined, cp2.rollbacks) \
            == (0, 1, 1, 0)


class TestPoolVersioning:
    @pytest.mark.parametrize("compress", COMPRESS)
    def test_rollback_restores_previous_version_bitwise(self, cfg, compress):
        pool = AdapterPool(3, cfg, rank=4, compress=compress, history=2)
        pool.register("u", make_adapters(cfg, 1), meta={"step": 4, "eval_loss": 2.0})
        v1 = slot_payload_np(pool, "u")
        pool.register("u", make_adapters(cfg, 2), meta={"step": 8, "eval_loss": 1.5})
        v2 = slot_payload_np(pool, "u")
        assert any(not np.array_equal(v1[n], v2[n]) for n in v1)
        assert pool.history_len("u") == 1
        assert pool.version_info("u") == {"step": 8, "eval_loss": 1.5, "history": 1}
        ver = pool.version
        meta = pool.rollback("u")
        assert meta == {"step": 4, "eval_loss": 2.0}
        assert pool.version == ver + 1      # serve idx memos must invalidate
        assert pool.stats.rollbacks == 1
        restored = slot_payload_np(pool, "u")
        for n in v1:    # storage layout archived -> bitwise even quantised
            np.testing.assert_array_equal(restored[n], v1[n], err_msg=n)
        assert pool.history_len("u") == 0
        with pytest.raises(KeyError, match="history"):
            pool.rollback("u")

    def test_history_depth_is_bounded(self, cfg):
        pool = AdapterPool(3, cfg, rank=4, history=2)
        for i in range(5):
            pool.register("u", make_adapters(cfg, 10 + i), meta={"step": i})
        assert pool.history_len("u") == 2
        assert pool.rollback("u") == {"step": 3, "eval_loss": None}
        assert pool.rollback("u") == {"step": 2, "eval_loss": None}
        with pytest.raises(KeyError, match="history"):
            pool.rollback("u")

    def test_eviction_drops_version_history(self, cfg):
        pool = AdapterPool(3, cfg, rank=4, history=2)  # 2 usable slots
        pool.register("a", make_adapters(cfg, 1))
        pool.register("a", make_adapters(cfg, 2))
        pool.register("b", make_adapters(cfg, 3))
        pool.register("c", make_adapters(cfg, 4))      # LRU-evicts a
        assert not pool.has("a")
        pool.register("a", make_adapters(cfg, 5))      # fresh again
        assert pool.history_len("a") == 0              # no stale archive
        with pytest.raises(KeyError):
            pool.rollback("a")

    def test_register_many_gate_suppresses_reregistration_only(self, cfg):
        pool = AdapterPool(4, cfg, rank=4, history=2)
        ad = {t: make_adapters(cfg, 20 + t) for t in range(3)}
        stack = jax.tree.map(lambda *xs: jnp.stack(xs), *(ad[t] for t in range(3)))
        pool.register_many([0, 1], jax.tree.map(lambda x: x[:2], stack))
        v0 = slot_payload_np(pool, 0)
        fresh = make_adapters(cfg, 30)
        stack2 = jax.tree.map(
            lambda *xs: jnp.stack(xs), ad[1], fresh, ad[0]
        )
        decisions = {1: "reject", 2: "reject", 0: "quarantine"}
        pool.register_many([1, 2, 0], stack2, gate=decisions.__getitem__)
        # Tenant 2 is FRESH: the gate has no served version to protect, so
        # its rows land even under a reject decision...
        assert pool.has(2)
        np.testing.assert_array_equal(
            slot_payload_np(pool, 2)["A"], np.asarray(stack2["A"][1])
        )
        # ...while the gated RE-registrations kept their old payloads.
        np.testing.assert_array_equal(slot_payload_np(pool, 0)["A"], v0["A"])
        assert pool.stats.gate_rejected == 1
        assert pool.history_len(1) == 0  # suppressed write: nothing archived

    @pytest.mark.parametrize("compress", COMPRESS)
    def test_state_roundtrip_carries_history(self, cfg, compress):
        pool = AdapterPool(3, cfg, rank=4, compress=compress, history=2)
        pool.register("u", make_adapters(cfg, 1), meta={"step": 2, "eval_loss": 3.0})
        pool.register("u", make_adapters(cfg, 2), meta={"step": 4, "eval_loss": 2.5})
        pool.register("v", make_adapters(cfg, 3), meta={"step": 2, "eval_loss": 9.0})
        twin = AdapterPool(3, cfg, rank=4, compress=compress, history=2)
        # The table rides a JSON manifest; round-trip it like a checkpoint.
        twin.load_state(
            pool.state_arrays(), json.loads(json.dumps(pool.slot_table()))
        )
        assert twin.version_info("u") == pool.version_info("u")
        assert twin.history_len("u") == 1 and twin.history_len("v") == 0
        a, b = pool.rollback("u"), twin.rollback("u")
        assert a == b
        for n, arr in slot_payload_np(pool, "u").items():
            np.testing.assert_array_equal(
                slot_payload_np(twin, "u")[n], arr, err_msg=n
            )

    @pytest.mark.parametrize("compress", COMPRESS)
    def test_loaded_twin_survives_source_writes(self, cfg, compress):
        # Slot writes donate the pool buffers, so ``load_state`` must copy
        # what it is given: writing to the source pool afterwards may
        # neither delete nor change the twin's arrays.
        pool = AdapterPool(3, cfg, rank=4, compress=compress, history=2)
        pool.register("u", make_adapters(cfg, 1), meta={"step": 2})
        twin = AdapterPool(3, cfg, rank=4, compress=compress, history=2)
        twin.load_state(
            pool.state_arrays(), json.loads(json.dumps(pool.slot_table()))
        )
        before = slot_payload_np(twin, "u")
        pool.register("u", make_adapters(cfg, 2), meta={"step": 4})
        pool.register("v", make_adapters(cfg, 3), meta={"step": 2})
        for n, arr in slot_payload_np(twin, "u").items():
            np.testing.assert_array_equal(arr, before[n], err_msg=n)
        twin.register("w", make_adapters(cfg, 4))   # its own live buffers
        assert twin.has("w") and not pool.has("w")


class TestGatedRuntime:
    def _adapted(self, cfg, params, control, **kw):
        rt = make_runtime(cfg, params, control=control, **kw)
        tokens, labels = make_data(cfg, 2, 8, 8)
        for t in range(2):
            rt.ingest(f"u{t}", tokens[t], labels[t])
        rt.adapt(epochs=1, batch_per_tenant=4, key=jax.random.key(3))
        return rt

    def test_reject_freezes_training_and_serving_state(self, cfg, params):
        rt = self._adapted(cfg, params, ALWAYS)
        assert {r["decision"] for _, r in rt.control_metrics()["tenants"]} \
            == {"accept"}  # first-ever write-back: nothing to protect
        step1 = rt.tenant("u0").step
        v1 = slot_payload_np(rt.pool.shards[0], "u0")
        rt.adapt(epochs=1, batch_per_tenant=4)
        rec = dict(rt.control_metrics()["tenants"])["u0"]
        assert rec["decision"] == "reject"
        assert rec["pre"] is not None and rec["post"] is not None
        assert rt.tenant("u0").step == step1          # training state frozen
        for n, arr in slot_payload_np(rt.pool.shards[0], "u0").items():
            np.testing.assert_array_equal(arr, v1[n])  # served slot kept
        assert rt.counters["control/reject"] == 2

    def test_quarantine_advances_state_but_serves_old(self, cfg, params):
        quar = ControlConfig(
            holdout_every=4, threshold=float("-inf"), mode="quarantine"
        )
        rt = self._adapted(cfg, params, quar)
        step1 = rt.tenant("u0").step
        v1 = slot_payload_np(rt.pool.shards[0], "u0")
        rt.adapt(epochs=1, batch_per_tenant=4)
        assert rt.control.is_quarantined("u0")
        assert rt.tenant("u0").step > step1           # training continues...
        for n, arr in slot_payload_np(rt.pool.shards[0], "u0").items():
            np.testing.assert_array_equal(arr, v1[n])  # ...serving does not
        assert rt.control_metrics()["quarantined_tenants"] == ["u0", "u1"]

    def test_streaming_adapt_path_evaluates_too(self, cfg, params):
        rt = self._adapted(cfg, params, NEVER, cache_capacity=8)
        out = rt.adapt(epochs=1, batch_per_tenant=4)
        assert out["path"] == "stream"
        rec = dict(rt.control_metrics()["tenants"])["u0"]
        assert rec["pre"] is not None and rec["post"] is not None
        assert rec["decision"] == "accept"
        assert rt.pool.history_len("u0") == 1         # accepted: archived

    def test_too_few_rows_passes_ungated(self, cfg, params):
        """A tenant below ``holdout_every`` rows has an empty eval set —
        it must adapt ungated (pre/post None), not crash the group."""
        rt = make_runtime(cfg, params, n_per=4, control=NEVER)
        tokens, labels = make_data(cfg, 1, 3, 8)
        rt.ingest("u0", tokens[0], labels[0])
        rt.adapt(epochs=1, batch_per_tenant=2, key=jax.random.key(3))
        rt.adapt(epochs=1, batch_per_tenant=2)
        rec = dict(rt.control_metrics()["tenants"])["u0"]
        assert rec["decision"] == "accept"
        assert rec["pre"] is None and rec["post"] is None

    def test_auto_rollback_fires_after_streak_and_resets_optimizer(
        self, cfg, params
    ):
        """threshold=-inf: the first write-back per tenant accepts, every
        later one rejects. With ``auto_rollback_after=2`` the second reject
        fires the automatic rollback: optimizer state zeroed, step reset,
        ledger counted — while the served slot (v1, never overwritten by
        the rejected versions) stays put."""
        control = ControlConfig(
            holdout_every=4, threshold=float("-inf"), auto_rollback_after=2
        )
        rt = self._adapted(cfg, params, control)       # adapt 1: accepts
        v1 = slot_payload_np(rt.pool.shards[0], "u0")
        rt.adapt(epochs=1, batch_per_tenant=4)         # reject, streak 1
        assert rt.control.auto_rollbacks == 0
        assert any(
            np.any(np.asarray(x)) for x in jax.tree.leaves(rt.tenant("u0").opt_mu)
        )
        rt.adapt(epochs=1, batch_per_tenant=4)         # reject, streak 2 -> fire
        assert rt.control.auto_rollbacks == 2          # both tenants
        assert rt.counters["control/auto_rollbacks"] == 2
        assert rt.counters["control/rollbacks"] == 2
        st = rt.tenant("u0")
        assert st.step == 0
        assert not any(
            np.any(np.asarray(x)) for x in jax.tree.leaves(st.opt_mu)
        )
        for n, arr in slot_payload_np(rt.pool.shards[0], "u0").items():
            np.testing.assert_array_equal(arr, v1[n])
        # The streak cleared with the rollback: one more reject is streak 1
        # again, no second firing.
        rt.adapt(epochs=1, batch_per_tenant=4)
        assert rt.control.auto_rollbacks == 2

    def test_auto_rollback_restores_archived_version(self, cfg, params):
        """With history beneath the served version, the automatic rollback
        restores it bitwise (the same mechanism the manual path uses)."""
        import dataclasses

        control = ControlConfig(
            holdout_every=4, threshold=float("inf"), auto_rollback_after=2
        )
        rt = self._adapted(cfg, params, control)       # v1 accepted
        v1 = slot_payload_np(rt.pool.shards[0], "u0")
        rt.adapt(epochs=1, batch_per_tenant=4)         # v2 accepted, v1 archived
        assert rt.pool.history_len("u0") == 1
        # The operator tightens the gate mid-session: every further
        # write-back now counts as a regression.
        rt.control.config = dataclasses.replace(
            rt.control.config, threshold=float("-inf")
        )
        rt.adapt(epochs=1, batch_per_tenant=4)         # reject, streak 1
        rt.adapt(epochs=1, batch_per_tenant=4)         # reject, streak 2 -> fire
        assert rt.control.auto_rollbacks == 2
        assert rt.pool.history_len("u0") == 0
        for n, arr in slot_payload_np(rt.pool.shards[0], "u0").items():
            np.testing.assert_array_equal(arr, v1[n])  # v2 rolled back to v1

    def test_control_off_keeps_historical_behaviour(self, cfg, params):
        rt = self._adapted(cfg, params, None)
        assert rt.control is None and rt.control_metrics() is None
        assert rt.pool.history_depth == 0
        with pytest.raises(KeyError):
            rt.pool.rollback("u0")

    def test_rollback_without_control_config_still_counts(self, cfg, params):
        rt = self._adapted(cfg, params, NEVER)
        rt.adapt(epochs=1, batch_per_tenant=4)        # v2 accepted, v1 archived
        before = dict(rt.control_metrics()["tenants"])["u0"]
        assert before is not None
        rt.rollback("u0")
        assert rt.counters["control/rollbacks"] == 1
        assert rt.control_metrics()["rollbacks"] == 1
        assert dict(rt.control_metrics()["tenants"]).get("u0") is None


class TestPoisonEndToEnd:
    """The ISSUE's acceptance bar, in-suite (the measured version lives in
    benchmarks/control_bench.py): a tenant whose recycled partition is
    refilled with constant-label garbage is gated on re-adapt; under an
    open gate the same poison lands and one rollback restores the previous
    version bitwise, eval record and served tokens included."""

    HOLD = 4

    def _poison(self, cfg, params, rows, seq):
        """All rows share one context; train rows carry random garbage
        labels while held-out rows keep the BASE model's own argmax (the
        distribution the tenant was serving well). Training on the garbage
        tears down exactly the calibration the held-out rows measure, so
        the regression is large and monotone — schemes with random held-out
        labels are confounded by the entropy-raising side effect of any
        training (a more uniform predictive distribution *lowers* expected
        loss on random targets)."""
        from repro.models.lm import lm_forward, readout

        rng = np.random.default_rng(23)
        row = rng.integers(0, cfg.vocab_size, (1, seq)).astype(np.int32)
        logits = readout(params, cfg, lm_forward(params, cfg, jnp.asarray(row))["h"])
        base_best = np.asarray(jnp.argmax(logits, -1)).astype(np.int32)
        garbage = rng.integers(0, cfg.vocab_size, (1, seq)).astype(np.int32)
        toks = np.repeat(row, rows, 0)
        labs = np.repeat(garbage, rows, 0)
        held = (np.arange(rows) + 1) % self.HOLD == 0
        labs[held] = base_best
        return toks, labs

    def _clean_session(self, cfg, params, control):
        rt = make_runtime(cfg, params, n_t=2, n_per=16, control=control)
        tokens, labels = make_data(cfg, 2, 16, 8)
        for t in range(2):
            rt.ingest(f"u{t}", tokens[t], labels[t])
        rt.adapt(epochs=2, batch_per_tenant=4, key=jax.random.key(3))
        return rt

    def _poison_victim(self, cfg, params, rt):
        rt.release("u0")                      # partition recycled, slot stays
        rt.ingest("u0", *self._poison(cfg, params, 16, 8))
        rt.adapt(["u0"], epochs=4, batch_per_tenant=4, key=jax.random.key(5))

    @pytest.mark.parametrize("mode", ["reject", "quarantine"])
    def test_gate_fires_and_served_slot_never_regresses(self, cfg, params, mode):
        ctl = ControlConfig(holdout_every=self.HOLD, threshold=0.0, mode=mode)
        rt = self._clean_session(cfg, params, ctl)
        clean_eval = rt.pool.version_info("u0")["eval_loss"]
        v_clean = slot_payload_np(rt.pool.shards[0], "u0")
        self._poison_victim(cfg, params, rt)
        rec = dict(rt.control_metrics()["tenants"])["u0"]
        assert rec["decision"] == mode and rec["delta"] > 0
        for n, arr in slot_payload_np(rt.pool.shards[0], "u0").items():
            np.testing.assert_array_equal(arr, v_clean[n], err_msg=n)
        # The SERVED version's recorded held-out loss never regressed.
        assert rt.pool.version_info("u0")["eval_loss"] == clean_eval
        assert rt.control.is_quarantined("u0") == (mode == "quarantine")

    def test_open_gate_poison_lands_and_rollback_restores(self, cfg, params):
        rt = self._clean_session(cfg, params, NEVER)
        prompts = jax.random.randint(jax.random.key(7), (1, 6), 0, cfg.vocab_size)
        v_clean = slot_payload_np(rt.pool.shards[0], "u0")
        clean_eval = rt.pool.version_info("u0")["eval_loss"]
        toks_clean = np.asarray(rt.serve(["u0"], prompts, max_new=6))
        self._poison_victim(cfg, params, rt)
        assert dict(rt.control_metrics()["tenants"])["u0"]["decision"] == "accept"
        toks_poisoned = np.asarray(rt.serve(["u0"], prompts, max_new=6))
        assert not np.array_equal(toks_clean, toks_poisoned)
        restored = rt.rollback("u0")
        assert restored["eval_loss"] == clean_eval
        for n, arr in slot_payload_np(rt.pool.shards[0], "u0").items():
            np.testing.assert_array_equal(arr, v_clean[n], err_msg=n)
        np.testing.assert_array_equal(
            np.asarray(rt.serve(["u0"], prompts, max_new=6)), toks_clean
        )


class TestControlCheckpoint:
    def _session_with_history(self, cfg, params, control):
        rt = make_runtime(cfg, params, control=control)
        tokens, labels = make_data(cfg, 2, 8, 8)
        for t in range(2):
            rt.ingest(f"u{t}", tokens[t], labels[t])
        rt.adapt(epochs=1, batch_per_tenant=4, key=jax.random.key(3))
        rt.adapt(epochs=1, batch_per_tenant=4)  # v2: v1 goes to history
        return rt

    def test_history_and_ledger_survive_restore(self, cfg, params, tmp_path):
        from repro.checkpoint.checkpoint import (
            restore_runtime_session,
            save_runtime_session,
        )

        rt = self._session_with_history(cfg, params, NEVER)
        assert rt.pool.history_len("u0") == 1
        path = save_runtime_session(str(tmp_path), 1, rt)
        rt_new = make_runtime(cfg, params, control=NEVER)
        restore_runtime_session(path, rt_new)
        assert rt_new.control_metrics() == rt.control_metrics()
        assert rt_new.pool.version_info("u0") == rt.pool.version_info("u0")
        assert rt_new.pool.history_len("u0") == 1
        # Rolling BOTH sessions back lands on the same bitwise payload and
        # the same served stream — the archive survived the manifest.
        a, b = rt.rollback("u0"), rt_new.rollback("u0")
        assert a == b
        prompts = jax.random.randint(jax.random.key(9), (1, 6), 0, cfg.vocab_size)
        np.testing.assert_array_equal(
            np.asarray(rt.serve(["u0"], prompts, max_new=4)),
            np.asarray(rt_new.serve(["u0"], prompts, max_new=4)),
        )

    def test_quarantine_set_survives_restore(self, cfg, params, tmp_path):
        from repro.checkpoint.checkpoint import (
            restore_runtime_session,
            save_runtime_session,
        )

        quar = ControlConfig(
            holdout_every=4, threshold=float("-inf"), mode="quarantine"
        )
        rt = self._session_with_history(cfg, params, quar)
        assert rt.control.is_quarantined("u0")
        path = save_runtime_session(str(tmp_path), 1, rt)
        rt_new = make_runtime(cfg, params, control=quar)
        restore_runtime_session(path, rt_new)
        assert rt_new.control.is_quarantined("u0")
        assert rt_new.control.quarantined == rt.control.quarantined

    def test_restore_into_uncontrolled_runtime_fails_loudly(
        self, cfg, params, tmp_path
    ):
        from repro.checkpoint.checkpoint import (
            restore_runtime_session,
            save_runtime_session,
        )

        rt = self._session_with_history(cfg, params, NEVER)
        path = save_runtime_session(str(tmp_path), 1, rt)
        with pytest.raises(ValueError, match="control"):
            restore_runtime_session(path, make_runtime(cfg, params))
