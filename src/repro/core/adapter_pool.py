"""Adapter pool: slot-based registry of per-tenant Skip-LoRA stacks.

The serving half of the Skip2-LoRA story (DESIGN.md §7): every user
fine-tunes their own adapter stack on-device, and the serving fleet must
apply a *different* stack per batch row. Because the skip topology taps
every layer input into the final output, the adapters can never be merged
into the backbone — so serving keeps them in a stacked device-resident pool

    A: (n_slots, L, D, R)    B: (n_slots, L, R, D)

indexed per request row by the grouped Pallas kernel
(``kernels.skip_lora.ops.skip_lora_grouped``). The pool mirrors the
``TieredCacheEngine`` slot design (§4): rows are *slots*, a host-side LRU
map assigns tenant -> slot, and registration past capacity evicts the
least-recently-served tenant. Slot 0 is pinned all-zeros — the "no adapter"
tenant, so base-model traffic rides the same batched kernel for free.

``compress="int8"`` stores the pool rowwise-quantised (int8 payload + fp32
scales over the last axis, the same scheme as the activation cache). The
quantised slots feed ``skip_lora_grouped_int8`` *raw*: dequant happens on
the gathered per-tile blocks in VMEM, so an int8 pool holds 4x the resident
tenants of a bf16 pool for the same HBM.

``compress="int4"`` / ``compress="nf4"`` halve the payload again: two 4-bit
codebook indices packed per byte (``kernels.skip_lora.quant``) + the same
fp32 rowwise scales, fed raw to ``skip_lora_grouped_q4`` (nibble unpack +
codebook dequant on the gathered blocks in VMEM). ``int4`` is uniform
symmetric; ``nf4`` uses the QLoRA NormalFloat4 levels, information-optimal
for the normally-distributed factors LoRA actually has. Either way the
zero slot stays EXACT zeros (scale 0), so base traffic is bitwise base.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lm_skiplora import quantize_int8
from repro.kernels.skip_lora import quant as q4
from repro.models.config import ModelConfig

Params = Any

#: In-place single-slot write: the pool array is donated, so a
#: registration costs one O(L*D*R) slot write, never a full-pool copy.
#: ``slot`` rides as a traced scalar so every slot shares one trace.
_set_slot = jax.jit(
    lambda arr, slot, val: arr.at[slot].set(val),
    donate_argnums=(0,),
)

#: pinned all-zeros slot: rows with no registered adapter (base model).
ZERO_SLOT = 0


#: Regression-gate decisions a write-back can carry (DESIGN.md §13).
#: "accept" installs the payload; "reject" and "quarantine" both leave the
#: slot serving its current version (the difference — whether the caller's
#: training state advances — is session policy, not pool mechanism).
GATE_DECISIONS = ("accept", "reject", "quarantine")


@dataclasses.dataclass
class PoolStats:
    registrations: int = 0
    evictions: int = 0
    lookups: int = 0
    misses: int = 0
    rollbacks: int = 0
    gate_rejected: int = 0
    gate_quarantined: int = 0

    def as_rows(self, prefix: str = "adapter_pool") -> list[tuple[str, float]]:
        return [
            (f"{prefix}/registrations", float(self.registrations)),
            (f"{prefix}/evictions", float(self.evictions)),
            (f"{prefix}/lookups", float(self.lookups)),
            (f"{prefix}/misses", float(self.misses)),
            (f"{prefix}/rollbacks", float(self.rollbacks)),
            (f"{prefix}/gate_rejected", float(self.gate_rejected)),
            (f"{prefix}/gate_quarantined", float(self.gate_quarantined)),
        ]


class AdapterPool:
    """Fixed-capacity device pool of per-tenant adapter stacks.

    Data plane: stacked jnp arrays consumed directly by the grouped kernel.
    Control plane: host-side LRU tenant->slot map, like the cache engine.
    """

    def __init__(
        self,
        n_slots: int,
        cfg: ModelConfig,
        rank: int,
        *,
        compress: Optional[str] = None,
        dtype=jnp.float32,
        device=None,
        history: int = 0,
    ):
        if n_slots < 2:
            raise ValueError("need >= 2 slots (slot 0 is pinned to zeros)")
        if compress not in (None, "int8") + q4.Q4_KINDS:
            raise ValueError(f"unknown compression {compress!r}")
        if history < 0:
            raise ValueError(f"history depth {history} < 0")
        self.n_slots = n_slots
        self.rank = rank
        self.compress = compress
        #: Versioned slots: how many *previous* payloads each tenant keeps
        #: (0 = versioning off, the historical pool). Each re-registration
        #: pushes the outgoing payload (in pool storage layout, so restores
        #: are bitwise) onto the tenant's bounded history; ``rollback``
        #: pops it back into the slot.
        self.history_depth = history
        #: Device the data plane is committed to (``None``: jax default).
        #: A mesh-native session commits each shard's pool to that shard's
        #: device, so serve/adapt dispatches against it stay device-local.
        self.device = device

        def z(shape, dt):
            arr = jnp.zeros(shape, dt)
            return jax.device_put(arr, device) if device is not None else arr

        l, d, r = cfg.n_layers, cfg.d_model, rank
        self._shape_a, self._shape_b = (l, d, r), (l, r, d)
        if compress in q4.Q4_KINDS:
            if r % 2 or d % 2:
                raise ValueError(
                    f"4-bit pools pack two indices per byte along the last "
                    f"axis: rank {r} and d_model {d} must both be even"
                )
            # Zero-init payload is nibble 0 (NOT the zero level), but the
            # zero-init SCALES make every unwritten slot dequantise to
            # exact zeros — code[0] * 0.0.
            self._qa4 = z((n_slots, l, d, r // 2), jnp.uint8)
            self._sa = z((n_slots, l, d), jnp.float32)
            self._qb4 = z((n_slots, l, r, d // 2), jnp.uint8)
            self._sb = z((n_slots, l, r), jnp.float32)
            self._code = z((16,), jnp.float32) + q4.codebook(compress)
        elif compress == "int8":
            self._qa = z((n_slots, l, d, r), jnp.int8)
            self._sa = z((n_slots, l, d), jnp.float32)
            self._qb = z((n_slots, l, r, d), jnp.int8)
            self._sb = z((n_slots, l, r), jnp.float32)
        else:
            self._a = z((n_slots, l, d, r), dtype)
            self._b = z((n_slots, l, r, d), dtype)
        # Slot 0 never enters the LRU / free list: it is the zero tenant.
        self._lru: OrderedDict[Any, int] = OrderedDict()
        self._free: list[int] = list(range(n_slots - 1, 0, -1))
        self._pinned: set = set()
        #: tenant -> oldest..newest previous-version records, each
        #: {"payload": {pool-array name: np.ndarray slot slice},
        #:  "step": int, "eval_loss": float|None}; bounded at
        #: ``history_depth`` entries per tenant.
        self._hist: dict[Any, list[dict]] = {}
        #: tenant -> {"step", "eval_loss"} of the *current* slot payload.
        self._vmeta: dict[Any, dict] = {}
        #: bumps whenever the tenant->slot map changes (new assignment,
        #: eviction, restore) — NOT on LRU touches, which keep slots stable.
        #: Callers may cache ``lookup`` results keyed on this (the session
        #: runtime memoises its serve-batch index arrays against it).
        self.version = 0
        self.stats = PoolStats()

    # -- capacity -----------------------------------------------------------

    def __len__(self) -> int:
        return len(self._lru)

    def tenants(self) -> list:
        return list(self._lru.keys())

    def has(self, tenant) -> bool:
        return tenant in self._lru

    def nbytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in self.pools().values())

    # -- registration -------------------------------------------------------

    def _write_slot(self, slot: int, adapters: Params) -> None:
        a = jnp.asarray(adapters["A"], jnp.float32)
        b = jnp.asarray(adapters["B"], jnp.float32)
        if a.shape != self._shape_a or b.shape != self._shape_b:
            raise ValueError(
                f"adapter shapes {a.shape}/{b.shape} != pool "
                f"{self._shape_a}/{self._shape_b}"
            )
        s = jnp.asarray(slot, jnp.int32)
        if self.compress in q4.Q4_KINDS:
            qa, sa = q4.quantize_q4(a, self.compress)
            qb, sb = q4.quantize_q4(b, self.compress)
            self._qa4 = _set_slot(self._qa4, s, qa)
            self._sa = _set_slot(self._sa, s, sa)
            self._qb4 = _set_slot(self._qb4, s, qb)
            self._sb = _set_slot(self._sb, s, sb)
        elif self.compress == "int8":
            qa, sa = quantize_int8(a)
            qb, sb = quantize_int8(b)
            self._qa = _set_slot(self._qa, s, qa)
            self._sa = _set_slot(self._sa, s, sa)
            self._qb = _set_slot(self._qb, s, qb)
            self._sb = _set_slot(self._sb, s, sb)
        else:
            self._a = _set_slot(self._a, s, a.astype(self._a.dtype))
            self._b = _set_slot(self._b, s, b.astype(self._b.dtype))

    def _assign_slot(self, tenant) -> int:
        """Control-plane half of registration: LRU bookkeeping only.
        Re-registration keeps the tenant's slot; a full pool evicts the
        least-recently-served *unpinned* tenant — a pinned slot (in-flight
        training state, see ``pin``) is never an eviction victim."""
        if tenant in self._lru:
            slot = self._lru[tenant]
            self._lru.move_to_end(tenant)
        else:
            if not self._free:
                victim = next(
                    (t for t in self._lru if t not in self._pinned), None
                )
                if victim is None:
                    raise RuntimeError(
                        f"pool full and all {len(self._lru)} resident tenants "
                        "pinned: cannot evict for a new registration"
                    )
                slot = self._lru.pop(victim)
                self._drop_versions(victim)
                self.stats.evictions += 1
            else:
                slot = self._free.pop()
            self._lru[tenant] = slot
            self.version += 1
        return slot

    # -- versioned slots (control plane, DESIGN.md §13) -----------------------

    def _payload_names(self) -> list[str]:
        """Per-slot pool arrays — everything ``pools()`` serves except the
        shared 4-bit codebook, which is a pool constant, not slot state."""
        return [n for n in self.pools() if n != "code"]

    def slot_payload(self, tenant) -> dict[str, jax.Array]:
        """The tenant's current slot content in storage layout (quantised
        pools stay quantised — the version a rollback would need to restore
        bitwise)."""
        slot = self._lru[tenant]
        return {n: self.pools()[n][slot] for n in self._payload_names()}

    def _push_history(self, tenant) -> None:
        """Archive the tenant's outgoing slot payload (+ its version meta)
        before an overwrite. Host copies: history must survive the donated
        in-place slot write that replaces the live buffers."""
        if self.history_depth < 1:
            return
        meta = self._vmeta.get(tenant, {})
        rec = {
            "payload": {
                n: np.asarray(v) for n, v in self.slot_payload(tenant).items()
            },
            "step": int(meta.get("step", 0)),
            "eval_loss": meta.get("eval_loss"),
        }
        h = self._hist.setdefault(tenant, [])
        h.append(rec)
        del h[: -self.history_depth]

    def _drop_versions(self, tenant) -> None:
        self._hist.pop(tenant, None)
        self._vmeta.pop(tenant, None)

    def history_len(self, tenant) -> int:
        return len(self._hist.get(tenant, ()))

    def version_info(self, tenant) -> dict:
        """{"step", "eval_loss", "history"} of the tenant's served version
        (KeyError if unregistered)."""
        if tenant not in self._lru:
            raise KeyError(f"tenant {tenant!r} has no registered adapters")
        meta = self._vmeta.get(tenant, {})
        return {
            "step": int(meta.get("step", 0)),
            "eval_loss": meta.get("eval_loss"),
            "history": self.history_len(tenant),
        }

    def set_eval_loss(self, tenant, eval_loss) -> None:
        """Stamp the served version's held-out loss (the gate's baseline
        record) without touching the payload."""
        if tenant not in self._lru:
            raise KeyError(f"tenant {tenant!r} has no registered adapters")
        meta = self._vmeta.setdefault(tenant, {"step": 0, "eval_loss": None})
        meta["eval_loss"] = None if eval_loss is None else float(eval_loss)

    def rollback(self, tenant) -> dict:
        """Restore the tenant's previous adapter version into its slot —
        bitwise, since history stores the storage-layout payload — and bump
        ``version`` so every slot-index memo keyed on it invalidates.
        Returns the restored version's {"step", "eval_loss"}. Raises
        KeyError when the tenant has no archived version to roll back to."""
        if tenant not in self._lru:
            raise KeyError(f"tenant {tenant!r} has no registered adapters")
        h = self._hist.get(tenant)
        if not h:
            raise KeyError(f"tenant {tenant!r} has no version history")
        rec = h.pop()
        if not h:
            del self._hist[tenant]
        s = jnp.asarray(self._lru[tenant], jnp.int32)
        for name, arr in rec["payload"].items():
            attr = "_" + name.lower()
            cur = getattr(self, attr)
            val = jnp.asarray(arr, cur.dtype)
            if self.device is not None:
                val = jax.device_put(val, self.device)
            setattr(self, attr, _set_slot(cur, s, val))
        self._vmeta[tenant] = {
            "step": rec["step"], "eval_loss": rec["eval_loss"]
        }
        self.version += 1
        self.stats.rollbacks += 1
        return {"step": rec["step"], "eval_loss": rec["eval_loss"]}

    # -- session pinning ----------------------------------------------------

    def pin(self, tenant) -> None:
        """Exclude a registered tenant's slot from LRU eviction. The session
        runtime pins every tenant with in-flight training state (adapters /
        optimizer moments mid-``adapt``), so a serve-traffic burst can never
        recycle a slot whose index is still baked into a queued fleet batch.
        Idempotent; raises KeyError for unregistered tenants."""
        if tenant not in self._lru:
            raise KeyError(f"tenant {tenant!r} has no registered adapters to pin")
        self._pinned.add(tenant)

    def unpin(self, tenant) -> None:
        """Re-admit a tenant's slot to LRU eviction (no-op if not pinned)."""
        self._pinned.discard(tenant)

    def pinned(self) -> set:
        return set(self._pinned)

    def register(self, tenant, adapters: Params, *, meta: Optional[dict] = None) -> int:
        """Install a tenant's fine-tuned {"A": (L,D,R), "B": (L,R,D)} stack.

        Re-registering overwrites in place (a fresh on-device fine-tune),
        archiving the outgoing payload when ``history > 0``. A full pool
        evicts the least-recently-served tenant. ``meta`` optionally stamps
        the new version's {"step", "eval_loss"}.

        The slot write donates the pool buffers (an in-place
        O(L*D*R) write, never a full-pool copy) — any dict previously
        returned by ``pools()`` is invalidated; re-fetch it after
        registration and never register mid-flight of a computation that
        still holds the old arrays.
        """
        if tenant in self._lru:
            self._push_history(tenant)
        slot = self._assign_slot(tenant)
        self._write_slot(slot, adapters)
        self._vmeta[tenant] = {
            "step": int((meta or {}).get("step", 0)),
            "eval_loss": (meta or {}).get("eval_loss"),
        }
        self.stats.registrations += 1
        return slot

    def register_many(
        self,
        tenants,
        stacked: Params,
        *,
        gate=None,
        meta: Optional[dict] = None,
    ) -> list[int]:
        """Batched registration of a fleet-trained stack: tenant
        ``tenants[i]`` gets ``{"A": stacked["A"][i], "B": stacked["B"][i]}``
        installed via ONE donated scatter per pool array (the fleet
        trainer's write-back path — an in-place O(T*L*D*R) write, never a
        full-pool copy, same donation caveats as ``register``). Returns the
        assigned slots, LRU/eviction semantics identical to T sequential
        ``register`` calls.

        ``gate`` is the control plane's write-back hook (DESIGN.md §13): a
        callable ``tenant -> decision`` drawn from ``GATE_DECISIONS``,
        consulted only for *re*-registrations (a fresh tenant has no served
        version to protect, so its first write-back always lands). A
        non-"accept" decision drops the tenant's rows from the scatter —
        the slot keeps serving the previous version bitwise — and bumps the
        matching gate counter. ``meta`` maps tenant -> {"step", "eval_loss"}
        stamped onto versions that do land."""
        tenants = list(tenants)
        if len(set(tenants)) != len(tenants):
            raise ValueError("duplicate tenants in batched registration")
        if len(tenants) > self.n_slots - 1:
            raise ValueError(
                f"{len(tenants)} tenants exceed pool capacity {self.n_slots - 1}"
            )
        a = jnp.asarray(stacked["A"], jnp.float32)
        b = jnp.asarray(stacked["B"], jnp.float32)
        if (
            a.shape != (len(tenants),) + self._shape_a
            or b.shape != (len(tenants),) + self._shape_b
        ):
            raise ValueError(
                f"stacked shapes {a.shape}/{b.shape} != "
                f"{(len(tenants),) + self._shape_a}/{(len(tenants),) + self._shape_b}"
            )
        write_idx: list[int] = []
        for i, t in enumerate(tenants):
            decision = "accept"
            if gate is not None and t in self._lru:
                decision = gate(t)
                if decision not in GATE_DECISIONS:
                    raise ValueError(f"gate decision {decision!r} for {t!r}")
            if decision == "accept":
                if t in self._lru:
                    self._push_history(t)
                write_idx.append(i)
            elif decision == "reject":
                self.stats.gate_rejected += 1
            else:
                self.stats.gate_quarantined += 1
        writes = set(write_idx)
        slots = []
        for i, t in enumerate(tenants):
            if i in writes:
                slots.append(self._assign_slot(t))
                self._vmeta[t] = {
                    "step": int((meta or {}).get(t, {}).get("step", 0)),
                    "eval_loss": (meta or {}).get(t, {}).get("eval_loss"),
                }
            else:
                # Gated out: slot, payload, and version meta all stay on the
                # previous version; still an LRU touch (the tenant was live).
                self._lru.move_to_end(t)
                slots.append(self._lru[t])
        if not write_idx:
            return slots
        if len(write_idx) < len(tenants):
            w = np.asarray(write_idx)
            a, b = a[w], b[w]
        sv = jnp.asarray([slots[i] for i in write_idx], jnp.int32)
        if self.compress in q4.Q4_KINDS:
            # Rowwise (last-axis) quantisation is per-slot independent, so
            # quantising the whole stack at once matches per-slot writes.
            qa, sa = q4.quantize_q4(a, self.compress)
            qb, sb = q4.quantize_q4(b, self.compress)
            self._qa4 = _set_slot(self._qa4, sv, qa)
            self._sa = _set_slot(self._sa, sv, sa)
            self._qb4 = _set_slot(self._qb4, sv, qb)
            self._sb = _set_slot(self._sb, sv, sb)
        elif self.compress == "int8":
            qa, sa = quantize_int8(a)
            qb, sb = quantize_int8(b)
            self._qa = _set_slot(self._qa, sv, qa)
            self._sa = _set_slot(self._sa, sv, sa)
            self._qb = _set_slot(self._qb, sv, qb)
            self._sb = _set_slot(self._sb, sv, sb)
        else:
            self._a = _set_slot(self._a, sv, a.astype(self._a.dtype))
            self._b = _set_slot(self._b, sv, b.astype(self._b.dtype))
        self.stats.registrations += len(write_idx)
        return slots

    def evict(self, tenant) -> None:
        if tenant in self._pinned:
            raise ValueError(
                f"tenant {tenant!r} is pinned (in-flight training state); "
                "unpin before evicting"
            )
        slot = self._lru.pop(tenant)
        self._drop_versions(tenant)
        self._free.append(slot)
        self.version += 1
        self.stats.evictions += 1

    # -- lookup -------------------------------------------------------------

    def lookup(self, tenants) -> jax.Array:
        """Tenant ids -> (B,) int32 slot indices for the grouped kernel.

        ``None`` maps to the pinned zero slot (base model, no adapter);
        unknown tenants raise — the serving tier decides whether a miss
        means "fine-tune first" or "serve base", not the pool.
        """
        slots = []
        for t in tenants:
            self.stats.lookups += 1
            if t is None:
                slots.append(ZERO_SLOT)
            elif t in self._lru:
                self._lru.move_to_end(t)
                slots.append(self._lru[t])
            else:
                self.stats.misses += 1
                raise KeyError(f"tenant {t!r} has no registered adapters")
        return jnp.asarray(slots, jnp.int32)

    def touch(self, tenants) -> None:
        """LRU-refresh only (no slot-index build): the runtime's memoised
        serve path calls this on cache hits so recency still tracks real
        serving traffic."""
        for t in tenants:
            if t is not None and t in self._lru:
                self._lru.move_to_end(t)

    # -- data plane ---------------------------------------------------------

    def pools(self) -> dict[str, jax.Array]:
        """The stacked arrays the grouped kernel consumes, in storage layout.

        float pool: {"A", "B"}; int8 pool: {"qa", "sa", "qb", "sb"};
        4-bit pool: {"qa4", "sa", "qb4", "sb", "code"} — quantised payloads
        are handed over *raw* (dequant lives in the kernel; ``code`` is the
        16-entry codebook that distinguishes int4 from nf4).
        The dict is a snapshot of the live buffers: ``register`` donates
        them, so re-fetch after any registration (see ``register``).
        """
        if self.compress in q4.Q4_KINDS:
            return {
                "qa4": self._qa4, "sa": self._sa,
                "qb4": self._qb4, "sb": self._sb, "code": self._code,
            }
        if self.compress == "int8":
            return {"qa": self._qa, "sa": self._sa, "qb": self._qb, "sb": self._sb}
        return {"A": self._a, "B": self._b}

    # -- session state (checkpoint plane) ------------------------------------

    def slot_table(self) -> dict:
        """JSON-able control plane: LRU-ordered (tenant, slot) pairs, free
        list, pinned tenants, plus the versioning plane — per-tenant version
        meta and history *metadata* ([step, eval_loss] per archived version,
        oldest..newest; payload arrays travel via ``state_arrays``, keyed
        ``hist/h{j}`` in the same LRU x depth enumeration order). Tenant ids
        must be JSON-serialisable for this to round-trip through a
        checkpoint manifest."""
        return {
            "lru": [[t, s] for t, s in self._lru.items()],
            "free": list(self._free),
            "pinned": [t for t in self._lru if t in self._pinned],
            "history_depth": self.history_depth,
            "meta": [
                [t, [m["step"], m["eval_loss"]]]
                for t, m in ((t, self._vmeta[t]) for t in self._lru)
                if t in self._vmeta
            ],
            "history": [
                [t, [[r["step"], r["eval_loss"]] for r in self._hist[t]]]
                for t in self._lru
                if self._hist.get(t)
            ],
        }

    def _hist_enumeration(self) -> list[tuple[Any, int]]:
        """(tenant, depth-index) pairs in the deterministic order history
        payload arrays are keyed under in ``state_arrays`` — LRU order,
        oldest..newest within a tenant — matching ``slot_table()``'s
        "history" entry row for row."""
        out = []
        for t in self._lru:
            for j in range(len(self._hist.get(t, ()))):
                out.append((t, j))
        return out

    def state_arrays(self) -> dict:
        """Everything array-valued a checkpoint must carry: the data plane
        under "data" (``pools()`` layout) and archived version payloads
        under "hist" as flat ``h{k}/{name}`` sub-dicts (enumeration order
        per ``_hist_enumeration``; metadata to reassemble lives in
        ``slot_table()``)."""
        hist = {}
        for k, (t, j) in enumerate(self._hist_enumeration()):
            hist[f"h{k}"] = dict(self._hist[t][j]["payload"])
        return {"data": dict(self.pools()), "hist": hist}

    def load_state(self, arrays: dict, table: dict) -> None:
        """Restore the data plane and control plane saved from a pool of
        identical geometry — the checkpoint restore path. ``arrays`` is a
        ``state_arrays()`` layout ({"data": ..., "hist": ...}); a flat
        ``pools()`` dict (the pre-versioning layout) is also accepted, with
        no history."""
        if "data" in arrays:
            data = arrays["data"]
            hist_payloads = arrays.get("hist", {})
        else:
            data, hist_payloads = arrays, {}
        want = set(self.pools())
        if set(data) != want:
            raise ValueError(f"pool arrays {set(data)} != expected {want}")
        for name, arr in data.items():
            cur = self.pools()[name]
            # A copy, never the caller's buffer: slot writes donate the pool
            # arrays, and ``arrays`` may be another live pool's
            # ``state_arrays()``.
            arr = jnp.array(arr, cur.dtype)
            if arr.shape != cur.shape:
                raise ValueError(
                    f"pool array {name}: {arr.shape} != {cur.shape}"
                )
            if self.device is not None:
                arr = jax.device_put(arr, self.device)
            setattr(self, "_" + name.lower(), arr)
        self._lru = OrderedDict((t, int(s)) for t, s in table["lru"])
        self._free = [int(s) for s in table["free"]]
        self._pinned = set(table.get("pinned", ()))
        self._vmeta = {
            t: {"step": int(step), "eval_loss": loss}
            for t, (step, loss) in table.get("meta", [])
        }
        self._hist = {}
        hist_meta = {t: metas for t, metas in table.get("history", [])}
        k = 0
        for t in self._lru:
            for step, loss in hist_meta.get(t, ()):
                payload = hist_payloads.get(f"h{k}")
                if payload is None:
                    raise ValueError(
                        f"history payload h{k} (tenant {t!r}) missing from "
                        "checkpoint arrays"
                    )
                self._hist.setdefault(t, []).append({
                    "payload": {n: np.asarray(v) for n, v in payload.items()},
                    "step": int(step),
                    "eval_loss": loss,
                })
                k += 1
        if k != len(hist_payloads):
            raise ValueError(
                f"{len(hist_payloads)} history payloads in checkpoint, "
                f"manifest accounts for {k}"
            )
        self.version += 1


# ---------------------------------------------------------------------------
# Mesh-native pool: slot -> shard placement over per-shard AdapterPools
# ---------------------------------------------------------------------------


class ShardedAdapterPool:
    """Adapter registry sharded along the mesh's ``data`` axis by tenant.

    Owns the slot->shard placement rule of the mesh-native session
    (DESIGN.md §10): every tenant is *placed* on a logical shard the first
    time the session sees it (balanced round-robin — the shard with the
    fewest placed tenants, lowest index on ties), and its pool slot, cache
    partition, training state, and serve rows live on that shard for the
    rest of the session. Each logical shard holds its own fixed-capacity
    ``AdapterPool`` committed to the shard's physical device, so grouped
    serve/adapt batches route rows to the shard holding their slot and
    never gather adapters across devices.

    Placement is *logical*: the number of shards is a session-layout
    property, fixed at construction and carried through checkpoints, while
    the physical device of shard ``s`` is ``devices[s % len(devices)]`` —
    which is what makes an elastic restore onto a different device count
    bitwise (same group traces, different placement only).

    With ``n_shards == 1`` every delegating method is exactly the wrapped
    single ``AdapterPool`` — the PR 4 serving path, bitwise.
    """

    def __init__(
        self,
        n_slots_per_shard: int,
        cfg: ModelConfig,
        rank: int,
        *,
        n_shards: int = 1,
        devices: Optional[list] = None,
        compress: Optional[str] = None,
        dtype=jnp.float32,
        history: int = 0,
    ):
        if n_shards < 1:
            raise ValueError(f"need >= 1 shard, got {n_shards}")
        devs = list(devices) if devices else [None]
        self.n_shards = n_shards
        self.compress = compress
        self.history_depth = history
        self.shards = [
            AdapterPool(
                n_slots_per_shard, cfg, rank, compress=compress, dtype=dtype,
                device=devs[s % len(devs)], history=history,
            )
            for s in range(n_shards)
        ]
        self._placement: dict[Any, int] = {}

    # -- placement (the rule this class owns) --------------------------------

    def place(self, tenant) -> int:
        """Assign (or return) the tenant's logical shard: balanced
        round-robin at first sight, sticky afterwards."""
        s = self._placement.get(tenant)
        if s is None:
            counts = [0] * self.n_shards
            for sh in self._placement.values():
                counts[sh] += 1
            s = min(range(self.n_shards), key=lambda i: (counts[i], i))
            self._placement[tenant] = s
        return s

    def shard_of(self, tenant) -> int:
        """The tenant's placed shard (``None`` -> shard 0, the zero slot)."""
        if tenant is None:
            return 0
        s = self._placement.get(tenant)
        if s is None:
            raise KeyError(f"tenant {tenant!r} has no shard placement")
        return s

    def unplace(self, tenant) -> None:
        self._placement.pop(tenant, None)

    def placement(self) -> dict:
        return dict(self._placement)

    def route(self, tenants) -> list[tuple[list[int], list]]:
        """Split a serve batch by slot shard: returns, per shard, the
        (original row positions, tenants) of the rows it owns. Base rows
        (``None``) ride shard 0's pinned zero slot."""
        out: list[tuple[list[int], list]] = [([], []) for _ in range(self.n_shards)]
        for pos, t in enumerate(tenants):
            rows, subs = out[self.shard_of(t)]
            rows.append(pos)
            subs.append(t)
        return out

    # -- single-shard delegation (the PR 4 surface) ---------------------------

    def _only(self) -> AdapterPool:
        if self.n_shards != 1:
            raise RuntimeError(
                "multi-shard pool: use route()/shard_pools(s)/lookup_local()"
            )
        return self.shards[0]

    def pools(self) -> dict[str, jax.Array]:
        return self._only().pools()

    def lookup(self, tenants) -> jax.Array:
        return self._only().lookup(tenants)

    def shard_pools(self, s: int) -> dict[str, jax.Array]:
        return self.shards[s].pools()

    def lookup_local(self, s: int, tenants) -> jax.Array:
        """Shard-local slot indices for a routed sub-batch."""
        return self.shards[s].lookup(tenants)

    # -- registry surface (routed by placement) -------------------------------

    def has(self, tenant) -> bool:
        s = self._placement.get(tenant)
        return s is not None and self.shards[s].has(tenant)

    def tenants(self) -> list:
        return [t for p in self.shards for t in p.tenants()]

    def __len__(self) -> int:
        return sum(len(p) for p in self.shards)

    def nbytes(self) -> int:
        return sum(p.nbytes() for p in self.shards)

    @property
    def version(self) -> int:
        """Monotone under every shard's slot-map change (memo key)."""
        return sum(p.version for p in self.shards)

    @property
    def stats(self) -> PoolStats:
        agg = PoolStats()
        for p in self.shards:
            agg.registrations += p.stats.registrations
            agg.evictions += p.stats.evictions
            agg.lookups += p.stats.lookups
            agg.misses += p.stats.misses
            agg.rollbacks += p.stats.rollbacks
            agg.gate_rejected += p.stats.gate_rejected
            agg.gate_quarantined += p.stats.gate_quarantined
        return agg

    def register(self, tenant, adapters: Params, *, meta: Optional[dict] = None) -> int:
        return self.shards[self.place(tenant)].register(
            tenant, adapters, meta=meta
        )

    def register_many(
        self,
        tenants,
        stacked: Params,
        *,
        gate=None,
        meta: Optional[dict] = None,
    ) -> list[int]:
        """Batched write-back, routed by placement. The mesh-native adapt
        path calls this with a same-shard group (one donated scatter on that
        shard's device); mixed groups split into one write per shard.
        ``gate``/``meta`` semantics per ``AdapterPool.register_many`` —
        both are tenant-keyed, so they pass through to shards unsplit."""
        tenants = list(tenants)
        by_shard: dict[int, list[int]] = {}
        for i, t in enumerate(tenants):
            by_shard.setdefault(self.place(t), []).append(i)
        slots = [0] * len(tenants)
        for s, rows in by_shard.items():
            if len(rows) == len(tenants):
                sub = stacked  # same-shard fast path: no gather
            else:
                # Route each shard's rows to ITS device: the source stack
                # may be committed elsewhere, and a committed-input scatter
                # into another shard's pool would be rejected by jit.
                ridx = jnp.asarray(rows)
                sub = jax.tree.map(lambda x: x[ridx], stacked)
                if self.shards[s].device is not None:
                    sub = jax.device_put(sub, self.shards[s].device)
            for i, slot in zip(rows, self.shards[s].register_many(
                    [tenants[i] for i in rows], sub, gate=gate, meta=meta)):
                slots[i] = slot
        return slots

    # -- versioned slots (routed by placement) --------------------------------

    def rollback(self, tenant) -> dict:
        return self.shards[self.shard_of(tenant)].rollback(tenant)

    def version_info(self, tenant) -> dict:
        return self.shards[self.shard_of(tenant)].version_info(tenant)

    def history_len(self, tenant) -> int:
        return self.shards[self.shard_of(tenant)].history_len(tenant)

    def set_eval_loss(self, tenant, eval_loss) -> None:
        self.shards[self.shard_of(tenant)].set_eval_loss(tenant, eval_loss)

    def evict(self, tenant) -> None:
        self.shards[self.shard_of(tenant)].evict(tenant)

    def pin(self, tenant) -> None:
        self.shards[self.shard_of(tenant)].pin(tenant)

    def unpin(self, tenant) -> None:
        s = self._placement.get(tenant)
        if s is not None:
            self.shards[s].unpin(tenant)

    def pinned(self) -> set:
        return set().union(*(p.pinned() for p in self.shards))

    def touch(self, tenants) -> None:
        for t in tenants:
            if t is not None and t in self._placement:
                self.shards[self._placement[t]].touch([t])

    # -- session state (checkpoint plane) ------------------------------------

    def state_arrays(self) -> dict:
        """Per-shard state (data plane + archived version payloads), keyed
        ``"s<shard>"`` (checkpoint layout)."""
        return {f"s{i}": p.state_arrays() for i, p in enumerate(self.shards)}

    def slot_table(self) -> dict:
        """JSON-able control plane: the placement map + per-shard tables."""
        return {
            "n_shards": self.n_shards,
            "placement": [[t, s] for t, s in self._placement.items()],
            "shards": [p.slot_table() for p in self.shards],
        }

    def load_state(self, arrays: dict, table: dict) -> None:
        if int(table["n_shards"]) != self.n_shards:
            raise ValueError(
                f"checkpoint has {table['n_shards']} pool shards, "
                f"this session is laid out for {self.n_shards} "
                "(logical shard count is a session-layout property; "
                "elastic restarts change devices, not shards)"
            )
        self._placement = {t: int(s) for t, s in table["placement"]}
        for i, p in enumerate(self.shards):
            p.load_state(arrays[f"s{i}"], table["shards"][i])


def grouped_skip_sum(
    acts: jax.Array,
    pools: dict[str, jax.Array],
    idx: jax.Array,
    *,
    use_kernel: bool = True,
    fused: bool = False,
) -> jax.Array:
    """Per-row skip-sum over a stacked pool: unpacks the pool layout (float,
    raw-int8, or packed-4-bit) and forwards to the grouped kernel wrappers,
    which own the row flattening, stop_gradient contract, and kernel/oracle
    dispatch.

    acts: (L, B, S, D); idx: (B,) int32 -> (B, S, D).

    ``fused=True`` skips the grouped Pallas dispatch and inlines the dense
    per-row gather + einsum instead — XLA then fuses the skip term straight
    into the enclosing (decode) program: no kernel-launch boundary, no
    sort/pad/scatter of B rows up to a (1 + groups) x tile buffer. At decode
    shape (a handful of rows) the padding dominates the kernel's work, so
    the fused form is the fast path; at prefill shape the grouped kernel
    wins and ``fused`` should stay off.
    """
    from repro.kernels.skip_lora.ops import (
        skip_lora_grouped,
        skip_lora_grouped_int8,
        skip_lora_grouped_q4,
    )
    from repro.runtime.sharding import constrain

    # Under a model-axis scope the stacked activations stay partitioned over
    # L: each shard contracts only its resident blocks' skip terms and GSPMD
    # stitches the (B, S, D) result with one reduce. No-op on 1-D meshes.
    acts = constrain(acts, "layers", None, None, None)
    use_kernel = use_kernel and not fused
    if "qa4" in pools:
        return skip_lora_grouped_q4(
            acts, pools["qa4"], pools["sa"], pools["qb4"], pools["sb"],
            pools["code"], idx, use_kernel=use_kernel,
        )
    if "qa" in pools:
        return skip_lora_grouped_int8(
            acts, pools["qa"], pools["sa"], pools["qb"], pools["sb"], idx,
            use_kernel=use_kernel,
        )
    return skip_lora_grouped(
        acts, pools["A"], pools["B"], idx, use_kernel=use_kernel
    )
