"""Continuous-batching request scheduler over ``SessionRuntime``.

The runtime's ``serve()``/``ingest()`` take pre-formed batches: every row
starts together, decodes in lockstep, and finishes together, so a device
serving live traffic either waits to fill a batch (latency) or decodes
alone (throughput). This module closes that gap — ROADMAP open item 1 —
with the request-level event loop the paper's deployment story assumes:
asynchronous per-tenant requests in, step-synchronous dispatches out,
fine-tuning interleaved at step boundaries.

Event model (one ``step()`` = one dispatch per shard with work):

  1. *Harvest*: pull the previous dispatch's token chunk to host, append
     per row, retire rows whose requests hit ``max_new`` (their batch rows
     are immediately recyclable).
  2. *Admit*: ``batch_plan.plan_admissions`` walks the arrival-ordered
     queue under the per-tenant in-flight cap (FIFO within tenant, no
     head-of-line blocking across tenants) and fills up to ``admit_bucket``
     free rows.
  3. *Dispatch*: with admissions, ONE fused jit runs the padded admission
     prefill (``lm.sched_prefill``), samples each new row's first token,
     scatters caches/tokens/positions into the live batch, and decodes a
     ``chunk``-step scan; without admissions, the chunk scan alone. Either
     way the decode is a scan of ``lm.decode_step`` — the same carry the
     fused ``decode_scan`` threads (the Lingvo ``Step`` idiom, SNIPPETS.md
     §3) — over per-row positions, per-row temperatures, and per-row
     adapter slots.
  4. *Train*: queued ``submit_ingest`` work runs between dispatches via
     ``SessionRuntime.ingest`` — the step-boundary interleaving bar.

Rows never wait for each other: a row admitted at step k decodes from its
own position while its neighbours are mid-sequence. Dead rows keep their
state frozen in-trace (``where(active, ...)``) so the dispatch geometry —
and therefore the compiled program — never changes: one ``sched_admit``
trace and one ``sched_step`` trace per (cfg, chunk, bucket) serve the whole
session, across every temperature in the traffic (temperature is traced,
never a static).

Determinism bars (tests/test_scheduler.py):

  - scan-of-``decode_step`` reproduces the fused ``decode_scan`` bitwise;
  - at temperature 0 a row admitted mid-decode produces exactly the tokens
    it produces decoded alone (batch-row independence + matched geometry:
    the live batch and the solo path see the same pad bucket and the same
    ``max_seq``);
  - sampling keys are counter-derived per dispatch (``fold_in(key(seed),
    n)``) — deterministic replay for a fresh identically-seeded scheduler,
    never a shared key between dispatches.

``mode="sequential"`` runs the SAME machinery but admits a request only
when the batch is empty — the one-request-at-a-time baseline the serving
benchmark compares against (``benchmarks/serving_bench.py``).
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter, deque
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batch_plan
from repro.core import runtime as RT
from repro.models.blocks import ATTN_KINDS
from repro.models.lm import (
    decode_step,
    pipeline_sched_prefill,
    sample_token,
    sched_prefill,
    sched_prefill_reuse,
)
from repro.runtime.sharding import scope_ctx

Params = Any

#: Sentinel batch row for admission padding: scatters with ``mode="drop"``
#: silently discard out-of-bounds rows, so padding an admission up to the
#: bucket width costs nothing and never perturbs live rows.
_DROP_ROW = 1 << 30


@dataclasses.dataclass
class Request:
    """One in-flight generation request (the scheduler's future)."""

    rid: int
    tenant: Any                        # None -> base model
    prompt: np.ndarray                 # (len,) int32
    max_new: int
    temperature: float = 0.0
    submitted_at: float = 0.0
    started_at: Optional[float] = None
    finished_at: Optional[float] = None
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    #: Terminal failure: the dispatch this request was admitted into raised.
    #: The request is ``done`` (it will never produce tokens) and ``result``
    #: re-raises the stored error.
    error: Optional[BaseException] = None

    def result(self) -> np.ndarray:
        if self.error is not None:
            raise RuntimeError(
                f"request {self.rid} failed in dispatch"
            ) from self.error
        if not self.done:
            raise RuntimeError(f"request {self.rid} still in flight")
        return np.asarray(self.tokens[: self.max_new], np.int32)

    @property
    def latency(self) -> float:
        if self.finished_at is None:
            raise RuntimeError(f"request {self.rid} still in flight")
        return self.finished_at - self.submitted_at


@dataclasses.dataclass
class IngestRequest:
    """Queued fine-tuning work, executed at the next step boundary."""

    rid: int
    tenant: Any
    tokens: jax.Array
    labels: jax.Array
    logits: Optional[jax.Array] = None
    done: bool = False


# ---------------------------------------------------------------------------
# Fused dispatch bodies (shared compiled-fn cache: one trace per geometry)
# ---------------------------------------------------------------------------


def _chunk_scan(params, cfg, use_kernel, fuse, chunk, pools, idx, caches, tok,
                pos, active, temps, key, max_seq):
    """``chunk`` decode steps over the live batch: a scan of ``decode_step``
    with per-row positions/temperatures/slots, dead rows frozen in place.
    Emits the token sampled at each step ((chunk, B)), unlike the fused
    ``decode_scan`` which emits the carried token — the host has already
    received every carried token, so emitting the new one means each chunk
    hands back exactly the tokens the host has not seen. ``fuse`` inlines
    each step's skip term as dense math (no grouped kernel dispatch inside
    the scan body) — temp-0 tokens are identical either way (tested)."""

    def body(carry, _):
        tok, pos, caches, key = carry
        (ntok, npos, caches, key), _ = decode_step(
            params, cfg, (tok, pos, caches, key),
            temperature=temps, pools=pools, idx=idx, use_kernel=use_kernel,
            fuse_skip=fuse,
        )
        # Freeze retired rows (their cache writes land at a frozen, clamped
        # position nobody will read) and clamp live positions so a chunk
        # overshooting a finishing row never scatters out of bounds.
        ntok = jnp.where(active[:, None], ntok, tok)
        npos = jnp.where(active, jnp.minimum(npos, max_seq - 1), pos)
        return (ntok, npos, caches, key), ntok[:, 0]

    (tok, pos, caches, key), toks = jax.lax.scan(
        body, (tok, pos, caches, key), None, length=chunk
    )
    return caches, tok, pos, toks


def _sched_step_fn(cfg, use_kernel: bool, chunk: int, max_seq: int,
                   fuse: bool = False, scope=None):
    def make():
        def f(params, pools, idx, caches, tok, pos, active, temps, key):
            RT._mark_trace("sched_step")
            with scope_ctx(scope):
                return _chunk_scan(
                    params, cfg, use_kernel, fuse, chunk, pools, idx, caches,
                    tok, pos, active, temps, key, max_seq,
                )

        return jax.jit(f, donate_argnums=(3,))

    return RT.compiled(
        ("sched_step", cfg, use_kernel, chunk, max_seq, fuse, scope), make
    )


def _sched_admit_fn(cfg, use_kernel: bool, chunk: int, max_seq: int,
                    bucket: int, prompt: int, fuse: bool = False, scope=None):
    def make():
        def f(params, pools, idx, new_tokens, new_lens, new_idx, new_rows,
              caches, tok, pos, active, temps, key):
            RT._mark_trace("sched_admit")
            with scope_ctx(scope):
                akey, key = jax.random.split(key)
                logits, new_caches = sched_prefill(
                    params, cfg, new_tokens, new_lens, pools, new_idx,
                    use_kernel=use_kernel,
                )
                b = tok.shape[0]
                row_t = jnp.take(temps, jnp.clip(new_rows, 0, b - 1))
                tok0, _ = sample_token(logits, akey, row_t)
                tok = tok.at[new_rows].set(tok0, mode="drop")
                pos = pos.at[new_rows].set(
                    new_lens.astype(pos.dtype), mode="drop"
                )
                caches = jax.tree.map(
                    lambda live, new: live.at[
                        ..., new_rows, 0:prompt, :, :
                    ].set(new.astype(live.dtype), mode="drop"),
                    caches, new_caches,
                )
                caches, tok, pos, toks = _chunk_scan(
                    params, cfg, use_kernel, fuse, chunk, pools, idx, caches,
                    tok, pos, active, temps, key, max_seq,
                )
                return caches, tok, pos, toks, tok0

        return jax.jit(f, donate_argnums=(7,))

    return RT.compiled(
        ("sched_admit", cfg, use_kernel, chunk, max_seq, bucket, prompt, fuse,
         scope),
        make,
    )


def _sched_admit_pipe_fn(cfg, use_kernel: bool, chunk: int, max_seq: int,
                         bucket: int, prompt: int, fuse: bool, scope,
                         n_micro: int):
    """Pipelined admission: the prefill runs as ``n_micro`` GPipe
    microbatches over the stage-split backbone (``pipeline_sched_prefill``,
    stages = the shard's model-axis devices), then the identical
    sample/scatter/chunk-scan tail as ``_sched_admit_fn``. The stage
    params/valid mask are jit *arguments* (leading axis sharded over the
    model axis), never trace constants."""

    def make():
        def f(params, stage_blocks, valid, pools, idx, new_tokens, new_lens,
              new_idx, new_rows, caches, tok, pos, active, temps, key):
            RT._mark_trace("sched_admit_pipe")
            with scope_ctx(scope):
                akey, key = jax.random.split(key)
                logits, new_caches = pipeline_sched_prefill(
                    params, cfg, stage_blocks, valid, new_tokens, new_lens,
                    pools, new_idx, mesh=scope.mesh, n_micro=n_micro,
                )
                b = tok.shape[0]
                row_t = jnp.take(temps, jnp.clip(new_rows, 0, b - 1))
                tok0, _ = sample_token(logits, akey, row_t)
                tok = tok.at[new_rows].set(tok0, mode="drop")
                pos = pos.at[new_rows].set(
                    new_lens.astype(pos.dtype), mode="drop"
                )
                caches = jax.tree.map(
                    lambda live, new: live.at[
                        ..., new_rows, 0:prompt, :, :
                    ].set(new.astype(live.dtype), mode="drop"),
                    caches, new_caches,
                )
                caches, tok, pos, toks = _chunk_scan(
                    params, cfg, use_kernel, fuse, chunk, pools, idx, caches,
                    tok, pos, active, temps, key, max_seq,
                )
                return caches, tok, pos, toks, tok0

        return jax.jit(f, donate_argnums=(9,))

    return RT.compiled(
        ("sched_admit_pipe", cfg, use_kernel, chunk, max_seq, bucket, prompt,
         fuse, scope, n_micro),
        make,
    )


def _sched_admit_reuse_fn(cfg, use_kernel: bool, chunk: int, max_seq: int,
                          bucket: int, prompt: int, tail: int, max_nb: int,
                          block: int, fuse: bool = False, scope=None):
    """Prefix-reuse admission: the wave's prompts all matched >= 1 pooled
    KV block, so the dispatch gathers their block tables out of the paged
    pool into fresh (A, P) admission caches (pure data movement — zero
    forward FLOPs for the prefix), prefills ONLY the (A, PT << P) tails
    through ``sched_prefill_reuse``, then runs the identical sample /
    scatter / chunk-scan epilogue as ``_sched_admit_fn``. Bitwise doctrine:
    cache dtype == compute dtype, so a gathered key is exactly the key a
    dense prefill would recompute — temp-0 tokens match reuse-off (gated in
    tests and ``benchmarks/serving_bench.py --prefix-share``)."""

    def make():
        def f(params, pools, idx, pool_data, tables, tail_tokens, tail_lens,
              prefix_lens, new_idx, new_rows, caches, tok, pos, active,
              temps, key):
            RT._mark_trace("sched_admit_reuse")
            with scope_ctx(scope):
                from repro.core.kv_pool import gather_blocks
                from repro.models.lm import init_serve_caches

                akey, key = jax.random.split(key)
                adm = init_serve_caches(cfg, bucket, prompt)
                prefix = gather_blocks(
                    pool_data, tables, block=block, use_kernel=use_kernel
                )
                span = max_nb * block
                adm = jax.tree.map(
                    lambda dst, src: dst.at[..., 0:span, :, :].set(
                        src.astype(dst.dtype)
                    ),
                    adm, prefix,
                )
                logits, new_caches = sched_prefill_reuse(
                    params, cfg, tail_tokens, tail_lens, prefix_lens, adm,
                    pools, new_idx, use_kernel=use_kernel,
                )
                b = tok.shape[0]
                row_t = jnp.take(temps, jnp.clip(new_rows, 0, b - 1))
                tok0, _ = sample_token(logits, akey, row_t)
                tok = tok.at[new_rows].set(tok0, mode="drop")
                pos = pos.at[new_rows].set(
                    (prefix_lens + tail_lens).astype(pos.dtype), mode="drop"
                )
                caches = jax.tree.map(
                    lambda live, new: live.at[
                        ..., new_rows, 0:prompt, :, :
                    ].set(new.astype(live.dtype), mode="drop"),
                    caches, new_caches,
                )
                caches, tok, pos, toks = _chunk_scan(
                    params, cfg, use_kernel, fuse, chunk, pools, idx, caches,
                    tok, pos, active, temps, key, max_seq,
                )
                return caches, tok, pos, toks, tok0

        return jax.jit(f, donate_argnums=(10,))

    return RT.compiled(
        ("sched_admit_reuse", cfg, use_kernel, chunk, max_seq, bucket, prompt,
         tail, max_nb, block, fuse, scope),
        make,
    )


# ---------------------------------------------------------------------------
# Live batch (per shard)
# ---------------------------------------------------------------------------


class _LiveBatch:
    """One shard's resident decode state: device carries (caches, tok, pos)
    plus host-side row bookkeeping. ``rows[i]`` is the request occupying
    batch row ``i`` (None = free)."""

    def __init__(self, cfg, max_batch: int, max_seq: int, device):
        from repro.models.lm import init_serve_caches

        if isinstance(device, jax.sharding.Sharding):
            # 2-D shard: the "device" is a replicated NamedSharding over the
            # shard's model-axis group (jax.default_device only accepts a
            # bare Device) — commit the fresh state onto the whole group.
            self.caches = jax.device_put(
                init_serve_caches(cfg, max_batch, max_seq), device
            )
            self.tok = jax.device_put(
                jnp.zeros((max_batch, 1), jnp.int32), device
            )
            self.pos = jax.device_put(jnp.zeros((max_batch,), jnp.int32), device)
        else:
            with jax.default_device(device):
                self.caches = init_serve_caches(cfg, max_batch, max_seq)
                self.tok = jnp.zeros((max_batch, 1), jnp.int32)
                self.pos = jnp.zeros((max_batch,), jnp.int32)
        self.rows: list[Optional[Request]] = [None] * max_batch
        self.active = np.zeros((max_batch,), bool)
        self.temps = np.zeros((max_batch,), np.float32)
        self.idx = np.zeros((max_batch,), np.int32)
        self.idx_version: Optional[int] = None
        #: Per-row prefix pin: ``(index, handle)`` while the row reuses
        #: pooled KV blocks, released when the row retires.
        self.blocks: list[Optional[tuple]] = [None] * max_batch

    def free_rows(self) -> list[int]:
        return [i for i, r in enumerate(self.rows) if r is None]

    def n_active(self) -> int:
        return int(self.active.sum())


class RequestScheduler:
    """Admission queue + continuous-batching event loop over a runtime.

    ``max_prompt`` is the single pad bucket every prompt is right-padded
    to; ``max_seq = max_prompt + max_new_cap`` sizes the live KV caches.
    ``inflight_per_tenant`` caps one tenant's simultaneous batch rows;
    ``admit_bucket`` is the (padded, so geometry-stable) admission width of
    one dispatch; ``chunk`` is how many decode steps each dispatch scans.
    ``mode="sequential"`` degrades the same loop to one-request-at-a-time
    (the benchmark baseline)."""

    def __init__(
        self,
        runtime,
        *,
        max_batch: int = 8,
        max_prompt: int = 16,
        max_new_cap: int = 32,
        admit_bucket: int = 2,
        inflight_per_tenant: int = 2,
        chunk: int = 4,
        mode: str = "continuous",
        microbatch: int = 0,
        prefix_reuse: bool = True,
        kv_block: Optional[int] = None,
        kv_pool_blocks: Optional[int] = None,
    ):
        if mode not in ("continuous", "sequential"):
            raise ValueError(f"unknown scheduler mode {mode!r}")
        kinds = set(runtime.cfg.layer_kinds())
        if not kinds <= set(ATTN_KINDS):
            raise NotImplementedError(
                f"scheduler needs per-row decode positions, which only the "
                f"attention cache supports today; config has {sorted(kinds)}"
            )
        if admit_bucket > max_batch:
            raise ValueError(f"admit_bucket {admit_bucket} > max_batch {max_batch}")
        self.rt = runtime
        self.max_batch = max_batch
        self.max_prompt = max_prompt
        self.max_new_cap = max_new_cap
        self.max_seq = max_prompt + max_new_cap
        self.admit_bucket = admit_bucket
        self.inflight_per_tenant = inflight_per_tenant
        self.chunk = chunk
        self.mode = mode
        # Pipelined admission (runtime built with pipeline_stages=N): the
        # admission prefill runs as GPipe microbatches of ``microbatch``
        # rows each, so the dispatch width pads up to n_micro * microbatch
        # (_DROP_ROW rows, free). More microbatches per dispatch -> smaller
        # bubble: predicted_bubble() = (P-1)/(n_micro+P-1).
        stages = int(getattr(runtime, "pipeline_stages", 0) or 0)
        self.pipeline = stages > 1
        if self.pipeline:
            mb = int(microbatch) if microbatch else 1
            if mb < 1:
                raise ValueError(f"microbatch {microbatch} < 1")
            self.pipe_microbatch = mb
            self.n_micro = -(-admit_bucket // mb)
            self.admit_pad = self.n_micro * mb
        elif microbatch:
            raise ValueError(
                "microbatch is a pipelined-admission knob; the runtime was "
                "built without pipeline_stages"
            )
        else:
            self.admit_pad = admit_bucket
        # Paged-KV prefix reuse (both modes; pipelined admission keeps the
        # dense prefill — the GPipe stage split owns its own cache layout).
        # ``kv_block`` overrides the autotuned/default block size;
        # ``kv_pool_blocks`` overrides the pool sizing heuristic. The pool
        # and radix index live on the RUNTIME (one per shard), so a later
        # scheduler on the same runtime reuses what an earlier one
        # published; ``runtime.reset_prefix_cache()`` clears them.
        self.prefix_reuse = bool(prefix_reuse) and not self.pipeline
        self.kv_block = int(kv_block) if kv_block else None
        self.kv_pool_blocks = int(kv_pool_blocks) if kv_pool_blocks else None
        self.counters = Counter()
        self._pending: deque[Request] = deque()
        self._ingest_queue: deque[IngestRequest] = deque()
        self._completed: list[Request] = []
        self._batches: dict[int, _LiveBatch] = {}
        self._in_flight: Counter = Counter()
        self._next_rid = 0
        self._dispatches = 0
        self._base_key = jax.random.key(runtime.seed)

    # -- submission ----------------------------------------------------------

    def submit(self, tenant, prompt, *, max_new: int,
               temperature: float = 0.0) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0 or prompt.size > self.max_prompt:
            raise ValueError(
                f"prompt length {prompt.size} outside (0, {self.max_prompt}]"
            )
        if not 0 < max_new <= self.max_new_cap:
            raise ValueError(f"max_new {max_new} outside (0, {self.max_new_cap}]")
        req = Request(
            rid=self._next_rid, tenant=tenant, prompt=prompt, max_new=max_new,
            temperature=float(temperature), submitted_at=time.perf_counter(),
        )
        self._next_rid += 1
        self._pending.append(req)
        self.counters["submitted"] += 1
        return req

    def submit_ingest(self, tenant, tokens, labels) -> IngestRequest:
        req = IngestRequest(
            rid=self._next_rid, tenant=tenant, tokens=tokens, labels=labels
        )
        self._next_rid += 1
        self._ingest_queue.append(req)
        return req

    # -- shard routing -------------------------------------------------------

    def _scope_of(self, shard: int):
        """The shard's ``ShardScope`` (None on 1-D sessions): rides every
        dispatch's compiled-fn key and wraps its trace so model-axis
        sessions bake the right activation constraints."""
        scopes = getattr(self.rt, "_scope", None)
        return None if scopes is None else scopes[shard]

    def predicted_bubble(self) -> Optional[float]:
        """GPipe bubble fraction the pipelined admission is scheduled at
        (None without pipelining) — the serving bench's bar for 'pipeline
        serve within the predicted bubble of the non-pipelined path'."""
        if not self.pipeline:
            return None
        from repro.runtime.pipeline_par import bubble_fraction

        return bubble_fraction(self.n_micro, self.rt.pipeline_stages)

    def quality_metrics(self) -> dict:
        """Control-plane gate events, shaped for the serving metrics
        surface: SLO dashboards read quality events (gate decisions,
        rollbacks, quarantines) next to latency. Empty gate section when
        the runtime has no control plane."""
        out: dict[str, Any] = {
            k.split("/", 1)[1]: int(v)
            for k, v in sorted(self.rt.counters.items())
            if k.startswith("control/")
        }
        cm = getattr(self.rt, "control_metrics", lambda: None)()
        if cm is not None:
            out["gate"] = {
                k: cm[k] for k in (
                    "accepted", "rejected", "quarantined", "rollbacks",
                    "auto_rollbacks",
                )
            }
            out["quarantined_tenants"] = cm["quarantined_tenants"]
        return out

    def _shard_of(self, tenant) -> int:
        """Serve placement: a tenant with a pool slot decodes on its slot's
        shard; base traffic and slot-less tenants ride shard 0's pinned
        zero slot (mirrors ``SessionRuntime.ingest``'s ``pool.has`` check,
        without creating placements for serve-only strangers)."""
        pool = self.rt.pool
        if tenant is not None and pool.has(tenant):
            return pool.shard_of(tenant)
        return 0

    def _batch(self, shard: int) -> _LiveBatch:
        lb = self._batches.get(shard)
        if lb is None:
            lb = self._batches[shard] = _LiveBatch(
                self.rt.cfg, self.max_batch, self.max_seq,
                self.rt._shard_device[shard],
            )
        return lb

    def _refresh_idx(self, shard: int, lb: _LiveBatch) -> None:
        """Re-resolve occupied rows' pool slots when the shard's slot map
        changed (an interleaved ``adapt`` bumps the version)."""
        version = self.rt.pool.shards[shard].version
        if lb.idx_version == version:
            return
        pool = self.rt.pool
        who = [
            r.tenant if r is not None and pool.has(r.tenant) else None
            for r in lb.rows
        ]
        lb.idx = np.asarray(pool.lookup_local(shard, who), np.int32)
        lb.idx_version = version

    # -- the event loop ------------------------------------------------------

    def step(self) -> int:
        """One scheduler tick: admit + dispatch on every shard with work,
        harvest the produced tokens, then run queued ingest work. Returns
        the number of dispatches issued."""
        plans = self._plan()
        issued = []
        for shard, admits in plans:
            issued.append(self._dispatch(shard, admits))
        done0 = self.counters["completed"]
        for shard, admits, out in issued:    # async dispatch, sync here
            self._harvest(shard, admits, out)
        # Row recycle: rows released by THIS step's retirements are
        # admissible immediately. Planning before the harvest meant a full
        # batch rejected admissible requests for one extra step even
        # though the dispatch about to land would free their rows — so
        # when the harvest retired something and work is still queued, run
        # one follow-up wave on shards with fresh admissions (a shard
        # without admissions is not re-dispatched; on one that is, the
        # other live rows simply advance an extra chunk — per-row decode
        # is asynchronous by construction, so that is just an extra tick).
        if self._pending and self.counters["completed"] > done0:
            extra = [
                self._dispatch(shard, admits)
                for shard, admits in self._plan() if admits
            ]
            for shard, admits, out in extra:
                self._harvest(shard, admits, out)
            if extra:
                self.counters["recycle_waves"] += 1
            issued.extend(extra)
        self._run_ingest()
        return len(issued)

    def drain(self) -> list[Request]:
        """Pump the loop until every queued request has completed; returns
        the requests completed during the drain, in completion order."""
        done0 = len(self._completed)
        while self._pending or self._ingest_queue or any(
            lb.n_active() for lb in self._batches.values()
        ):
            if self.step() == 0 and not self._ingest_queue:
                raise RuntimeError("scheduler stalled with queued work")
        return self._completed[done0:]

    def _plan(self) -> list[tuple[int, list[Request]]]:
        """Route the pending queue by shard and pick admissions per shard
        under the fairness policy. Sequential mode admits one request, and
        only into an idle batch."""
        plans: list[tuple[int, list[Request]]] = []
        pending = list(self._pending)
        total_active = sum(lb.n_active() for lb in self._batches.values())
        if self.mode == "sequential":
            # Globally one request at a time: admit the queue head only
            # into a fully idle system; otherwise just keep stepping the
            # shard holding the current request.
            if total_active == 0 and pending:
                plans.append((self._shard_of(pending[0].tenant), pending[:1]))
            else:
                plans.extend(
                    (s, []) for s, lb in sorted(self._batches.items())
                    if lb.n_active()
                )
            return plans
        by_shard: dict[int, list[Request]] = {}
        for r in pending:
            by_shard.setdefault(self._shard_of(r.tenant), []).append(r)
        shards = set(by_shard) | {
            s for s, lb in self._batches.items() if lb.n_active()
        }
        for shard in sorted(shards):
            lb = self._batch(shard)
            queue = by_shard.get(shard, [])
            picks = batch_plan.plan_admissions(
                queue, self._in_flight, len(lb.free_rows()),
                cap=self.inflight_per_tenant, bucket=self.admit_bucket,
            )
            admits = [queue[i] for i in picks]
            if admits or lb.n_active():
                plans.append((shard, admits))
        return plans

    def _prefix_state(self, shard: int):
        """(pool, index) for a shard's paged prefix cache, built lazily on
        the runtime. Disabled — ``(None, None)`` — when no full block can
        ever be matched (a match is capped at ``(len - 1) // block`` so a
        tail token survives; with ``block >= max_prompt`` that cap is
        always zero and the pool would be dead weight)."""
        from repro.core import kv_pool as KV

        blk = self.kv_block or KV.get_default_block()
        if (self.max_prompt - 1) // blk < 1:
            return None, None
        n_blocks = self.kv_pool_blocks or max(
            8, 2 * self.max_batch * (self.max_prompt // blk)
        )
        pool = self.rt.kv_pool(shard, block=blk, n_blocks=n_blocks)
        return pool, self.rt.prefix_index(shard)

    def _dispatch(self, shard: int, admits: list[Request]):
        lb = self._batch(shard)
        matches = None
        pool = pidx = None
        if admits and self.prefix_reuse:
            pool, pidx = self._prefix_state(shard)
            if pidx is not None:
                m = [pidx.match(r.tenant, r.prompt) for r in admits]
                # One dispatch is one geometry: split a mixed wave at the
                # first kind flip and take the longest same-kind FIFO
                # prefix (all-reuse or all-dense); the rest stay pending
                # for the next plan.
                want = bool(m[0])
                take = 1
                while take < len(admits) and bool(m[take]) == want:
                    take += 1
                if take < len(admits):
                    admits = admits[:take]
                    self.counters["prefix/wave_split"] += 1
                if want:
                    matches = m[:take]
        now = time.perf_counter()
        free = lb.free_rows()
        for req, row in zip(admits, free):
            self._pending.remove(req)
            lb.rows[row] = req
            lb.active[row] = True
            lb.temps[row] = req.temperature
            self._in_flight[req.tenant] += 1
            req.started_at = now
        lb.idx_version = None            # occupancy changed: re-resolve slots
        self._refresh_idx(shard, lb)
        params = self.rt._shard_params[shard]
        pools = self.rt.pool.shard_pools(shard)
        key = jax.random.fold_in(
            jax.random.fold_in(self._base_key, self._dispatches), shard
        )
        self._dispatches += 1
        scope = self._scope_of(shard)
        if admits:
            a, p = self.admit_pad, self.max_prompt
            rows = free[: len(admits)]
            if matches is not None:
                return self._dispatch_reuse(
                    shard, lb, admits, rows, matches, pool, pidx, params,
                    pools, key, scope,
                )
            new_tokens = np.zeros((a, p), np.int32)
            new_lens = np.ones((a,), np.int32)
            new_rows = np.full((a,), _DROP_ROW, np.int32)
            for j, (req, row) in enumerate(zip(admits, rows)):
                new_tokens[j, : req.prompt.size] = req.prompt
                new_lens[j] = req.prompt.size
                new_rows[j] = row
            new_idx = lb.idx[np.minimum(new_rows, self.max_batch - 1)]
            if self.pipeline:
                fn = _sched_admit_pipe_fn(
                    self.rt.cfg, self.rt.use_kernel, self.chunk, self.max_seq,
                    a, p, getattr(self.rt, "decode_fuse", False), scope,
                    self.n_micro,
                )
                args = (
                    params, self.rt._stage_blocks[shard],
                    self.rt._stage_valid[shard], pools, jnp.asarray(lb.idx),
                    new_tokens, new_lens, new_idx, new_rows, lb.caches,
                    lb.tok, lb.pos, lb.active, lb.temps, key,
                )
            else:
                fn = _sched_admit_fn(
                    self.rt.cfg, self.rt.use_kernel, self.chunk, self.max_seq,
                    a, p, getattr(self.rt, "decode_fuse", False), scope,
                )
                args = (
                    params, pools, jnp.asarray(lb.idx), new_tokens, new_lens,
                    new_idx, new_rows, lb.caches, lb.tok, lb.pos, lb.active,
                    lb.temps, key,
                )
            try:
                lb.caches, lb.tok, lb.pos, toks, tok0 = fn(*args)
            except Exception as err:
                self._abort_admits(lb, admits, rows, err)
                raise
            self.counters[
                "dispatch/admit_pipe" if self.pipeline else "dispatch/admit"
            ] += 1
            if pidx is not None:
                self._publish_rows(pool, pidx, lb, admits, rows)
                self.counters["prefix/misses"] += len(admits)
            return shard, list(zip(admits, rows)), (toks, tok0)
        fn = _sched_step_fn(
            self.rt.cfg, self.rt.use_kernel, self.chunk, self.max_seq,
            getattr(self.rt, "decode_fuse", False), scope,
        )
        lb.caches, lb.tok, lb.pos, toks = fn(
            params, pools, jnp.asarray(lb.idx), lb.caches, lb.tok, lb.pos,
            lb.active, lb.temps, key,
        )
        self.counters["dispatch/step"] += 1
        return shard, [], (toks, None)

    def _dispatch_reuse(self, shard: int, lb: _LiveBatch, admits, rows,
                        matches, pool, pidx, params, pools, key, scope):
        """Reuse-wave dispatch: every admit matched >= 1 pooled block. Pin
        the matched blocks for the rows' lifetimes, then one fused jit
        gathers them into the admission caches and prefills only the
        tails (``_sched_admit_reuse_fn``)."""
        a, p = self.admit_pad, self.max_prompt
        blk = pool.block
        nbs = [len(ids) for ids in matches]
        max_nb = max(nbs)
        tails = [r.prompt.size - nb * blk for r, nb in zip(admits, nbs)]
        # Tail pad bucket: block-quantised (trace reuse across waves whose
        # max tail rounds the same), never above the prompt bucket.
        pt = min(p, -(-max(tails) // blk) * blk)
        tables = np.zeros((a, max_nb), np.int32)
        tail_tokens = np.zeros((a, pt), np.int32)
        tail_lens = np.ones((a,), np.int32)
        prefix_lens = np.zeros((a,), np.int32)
        new_rows = np.full((a,), _DROP_ROW, np.int32)
        for j, (req, row, ids) in enumerate(zip(admits, rows, matches)):
            nb = len(ids)
            # Rows with nb < max_nb pad their table with block 0 — any
            # valid id: the padded key positions are >= the row's own
            # length, masked in the tail prefill and overwritten by
            # decode before it ever attends there.
            tables[j, :nb] = ids
            plen = nb * blk
            t = req.prompt[plen:]
            tail_tokens[j, : t.size] = t
            tail_lens[j] = t.size
            prefix_lens[j] = plen
            new_rows[j] = row
            lb.blocks[row] = (pidx, pidx.acquire(ids))
            self.counters["prefix/blocks_reused"] += nb
            self.counters["prefix/tokens_reused"] += plen
        self.counters["prefix/hits"] += len(admits)
        new_idx = lb.idx[np.minimum(new_rows, self.max_batch - 1)]
        fn = _sched_admit_reuse_fn(
            self.rt.cfg, self.rt.use_kernel, self.chunk, self.max_seq, a, p,
            pt, max_nb, blk, getattr(self.rt, "decode_fuse", False), scope,
        )
        try:
            lb.caches, lb.tok, lb.pos, toks, tok0 = fn(
                params, pools, jnp.asarray(lb.idx), pool.data, tables,
                tail_tokens, tail_lens, prefix_lens, new_idx, new_rows,
                lb.caches, lb.tok, lb.pos, lb.active, lb.temps, key,
            )
        except Exception as err:
            self._abort_admits(lb, admits, rows, err)
            raise
        self.counters["dispatch/admit_reuse"] += 1
        return shard, list(zip(admits, rows)), (toks, tok0)

    def _publish_rows(self, pool, pidx, lb: _LiveBatch, admits, rows) -> None:
        """After a dense admission lands, index the wave's full prompt
        blocks and publish their freshly-prefilled K/V out of the live
        rows into the pool (``floor(len / block)`` blocks per prompt;
        only newly-created radix nodes copy)."""
        for req, row in zip(admits, rows):
            created = pidx.insert(req.tenant, req.prompt)
            if created:
                pool.publish(
                    lb.caches, row,
                    [bid for bid, _ in created],
                    [slot for _, slot in created],
                )
                self.counters["prefix/published_blocks"] += len(created)

    def _release_blocks(self, lb: _LiveBatch, row: int) -> None:
        handle = lb.blocks[row]
        if handle is not None:
            lb.blocks[row] = None
            pidx, h = handle
            pidx.release(h)

    def prefix_metrics(self) -> dict:
        """Prefix-reuse observability for the serving bench: hit/miss and
        reused-block/token counters plus per-shard pool occupancy. After a
        drain (no rows in flight) every held block belongs to exactly one
        radix node, so ``refs_total == held == nodes`` — the no-leak gate
        (``SessionRuntime.check_prefix_no_leaks``)."""
        out: dict[str, Any] = {
            k.split("/", 1)[1]: int(v)
            for k, v in sorted(self.counters.items())
            if k.startswith("prefix/")
        }
        out["pools"] = {
            str(s): {
                "block": p.block,
                "n_blocks": p.n_blocks,
                "free": p.n_free(),
                "held": int((p.refs > 0).sum()),
                "refs_total": int(p.refs.sum()),
                "nodes": (
                    self.rt._prefix_indexes[s].n_nodes()
                    if s in getattr(self.rt, "_prefix_indexes", {}) else 0
                ),
            }
            for s, p in sorted(getattr(self.rt, "_kv_pools", {}).items())
        }
        return out

    def _abort_admits(self, lb: _LiveBatch, admits, rows, err) -> None:
        """Unwind a failed dispatch's admissions: the rows just claimed go
        back to the free list and each admitted tenant's in-flight count
        comes back down — otherwise one raising dispatch permanently leaks
        batch rows AND pins the tenant at its cap (every later admission of
        that tenant would be skipped forever). The requests are terminally
        failed (``error`` set; ``result()`` re-raises), not re-queued: the
        caller sees the raise and owns the retry policy."""
        now = time.perf_counter()
        for req, row in zip(admits, rows):
            self._release_blocks(lb, row)
            lb.rows[row] = None
            lb.active[row] = False
            self._in_flight[req.tenant] -= 1
            if self._in_flight[req.tenant] <= 0:
                del self._in_flight[req.tenant]
            req.done = True
            req.error = err
            req.finished_at = now
            self.counters["failed"] += 1
        lb.idx_version = None  # occupancy changed again: re-resolve slots

    def _harvest(self, shard: int, admitted, out) -> None:
        lb = self._batch(shard)
        toks, tok0 = out
        toks = np.asarray(toks)                      # (chunk, B) sync point
        if tok0 is not None:
            tok0 = np.asarray(tok0)                  # (bucket, 1)
            for j, (req, row) in enumerate(admitted):
                req.tokens.append(int(tok0[j, 0]))
        for row, req in enumerate(lb.rows):
            if req is None or not lb.active[row]:
                continue
            need = req.max_new - len(req.tokens)
            req.tokens.extend(int(t) for t in toks[: max(need, 0), row])
            if len(req.tokens) >= req.max_new:
                self._finish(lb, row, req)
        self.counters["steps"] += self.chunk

    def _finish(self, lb: _LiveBatch, row: int, req: Request) -> None:
        req.done = True
        req.finished_at = time.perf_counter()
        self._release_blocks(lb, row)
        lb.rows[row] = None
        lb.active[row] = False
        self._in_flight[req.tenant] -= 1
        if self._in_flight[req.tenant] <= 0:
            del self._in_flight[req.tenant]
        self._completed.append(req)
        self.counters["completed"] += 1
        self.counters["tokens"] += req.max_new

    def _run_ingest(self) -> None:
        while self._ingest_queue:
            req = self._ingest_queue.popleft()
            req.logits = self.rt.ingest(req.tenant, req.tokens, req.labels)
            req.done = True
            self.counters["ingested"] += 1
