"""Build the session a cell describes and drive its measured window.

The window drives ``SessionRuntime`` as a service would: requests go in
through ``enqueue_serve`` and the harness itself calls
``scheduler.step()``, stamping each request's first and last token as the
step that delivered it returns; fine-tune jobs go through ``ingest``,
``adapt`` and ``release``. Every call the harness makes into a layer is a
host span (``Spans``), also written into the profiler trace when one runs.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench import model as M
from bench import weights as W


class Spans:
    """Host spans ``(name, start, end)`` on ``time.perf_counter``; with
    ``annotate`` each is also a ``TraceAnnotation`` in the profiler."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self.annotate = False

    def __call__(self, name: str, fn, *args, **kw):
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                out = fn(*args, **kw)
        else:
            out = fn(*args, **kw)
        self.spans.append((name, t0, time.perf_counter()))
        return out


class _Finished:
    """What the check reads of a scheduler ``Request`` once the runtime is
    gone (host data only)."""

    def __init__(self, req):
        self.prompt, self.tokens = req.prompt, list(req.tokens)
        self.max_new, self.error, self.done = req.max_new, req.error, req.done


@dataclasses.dataclass
class Served:
    """A request as the harness follows it."""

    planned: gen.Planned
    due_at: float                     # absolute perf_counter time
    submitted_at: float = 0.0         # when the harness submitted it
    req: object = None                # the scheduler's Request
    first_at: Optional[float] = None
    last_at: Optional[float] = None
    seen: int = 0                     # tokens the host has seen
    win_lo: Optional[int] = None      # token indices the host saw inside
    win_hi: Optional[int] = None      # the window: [win_lo, win_hi)

    @property
    def done(self) -> bool:
        return self.last_at is not None


def runtime_options(opts: dict) -> dict:
    """A mix's ``runtime`` part as ``SessionRuntime`` keywords: all are
    passed through, and ``mesh`` (``{"shape": [...], "axes": [...]}``) is
    built over the process's devices."""
    opts = dict(opts)
    if "mesh" in opts:
        from repro.runtime.sharding import make_mesh

        m = opts["mesh"]
        opts["mesh"] = make_mesh(tuple(m["shape"]), tuple(m["axes"]))
    return opts


class Session:
    """One cell's system under test: weights and served adapters from the
    seed, the runtime, its scheduler, and the traffic streams. ``model``
    and ``reference`` are the modules the configuration names
    (``bench/model.py``)."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float, *,
                 root: Optional[str] = None):
        from repro.core import lm_skiplora as SL
        from repro.core.runtime import SessionRuntime

        root = root or os.path.dirname(gen.HERE)
        self.cfg, self.seed = cfg, seed
        self.model = M.model(cfg, root)
        self.reference = M.reference(cfg, root)
        self.mcfg = self.model.program_config(cfg)
        self.rt_seed = seed % 2**31
        self.serve = mix.get("serve")
        self.ft = mix.get("finetune")
        self.sched_kw = dict(mix.get("scheduler") or {})
        vocab = cfg["vocab_size"]
        self.stream = (
            gen.driver(self.serve["driver"], root).stream(
                self.serve, vocab=vocab, seed=seed, seconds=seconds,
                max_batch=self.sched_kw["max_batch"])
            if self.serve else None
        )
        self.jobs = (
            gen.driver(self.ft["driver"], root).stream(self.ft, vocab=vocab, seed=seed)
            if self.ft else None
        )
        self.params = self.model.make_params(cfg, seed)
        jax.block_until_ready(self.params)
        self.served_names = list(self.stream.tenants) if self.stream else []
        n_served = len(self.served_names)
        cohort = int(self.ft["cohort"]) if self.ft else 1
        self.rank = int(cfg["skip_lora_rank"])
        sl = SL.SkipLoRAConfig(
            rank=self.rank, mode="full", cache_dtype=cfg["skip_cache_dtype"],
            use_fused_kernel=True,
        )
        opts = dict(
            max_tenants=cohort,
            samples_per_tenant=int(self.ft["samples"]) if self.ft else 1,
            seq=int(self.ft["seq"]) if self.ft else 1,
            lr=float(cfg["adapt_lr"]), use_kernel=True,
            pool_slots=n_served + cohort + 1, seed=self.rt_seed,
        )
        opts.update(runtime_options(mix.get("runtime") or {}))
        self.rt = SessionRuntime(self.mcfg, sl, self.params, **opts)
        self.served_adapters = None
        if n_served:
            self.served_adapters = W.make_adapters(cfg, seed, n_served)
            self.rt.pool.register_many(self.served_names, self.served_adapters)
        self.sched = None
        if self.serve:
            self.sched = self.rt.attach_scheduler(**self.sched_kw)
        self.adapt_key = W.jax_key(seed, 3)
        self.spans = Spans()
        self.served: list[Served] = []
        self.jobs_done: list[dict] = []
        self.check_job: Optional[dict] = None
        self.window: tuple[float, float] = (0.0, 0.0)
        self.tokens_in_window = 0
        self.step_log: list[dict] = []

    # -- warm-up -------------------------------------------------------------

    def warm_up(self) -> None:
        """Compile every program the window runs, through the window's own
        calls: serve admissions and decode steps on a few requests, and one
        whole fine-tune cohort (which the correctness check then reads)."""
        if self.sched is not None:
            rng = np.random.default_rng([self.seed, 9])
            who = [None] + self.served_names[:3]
            sc = self.sched
            reqs = [
                self.rt.enqueue_serve(
                    t, rng.integers(0, self.cfg["vocab_size"],
                                    min(8 + i, sc.max_prompt), dtype=np.int32),
                    max_new=min(2 * sc.chunk + 1, sc.max_new_cap),
                )
                for i, t in enumerate(who)
            ]
            self.rt.drain()
            for r in reqs:
                r.result()
        if self.ft is not None:
            self.check_job = self._run_cohort(check=True)

    # -- fine-tune jobs ------------------------------------------------------

    def _cohort_pieces(self):
        """The calls of one cohort, in order, each a (name, fn) piece that
        ends when its result is ready."""
        ft = self.ft
        jobs = [self.jobs.next() for _ in range(int(ft["cohort"]))]
        names = [j.tenant for j in jobs]
        pieces = []
        for job in jobs:
            for toks, labs in job.batches:
                pieces.append(("ingest", self._ingest(job.tenant, toks, labs)))
        pieces.append(("adapt", self._adapt(names, int(ft["epochs"]))))
        pieces.append(("release", self._release(names)))
        return jobs, pieces

    def _ingest(self, tenant, toks, labs):
        def f():
            out = self.rt.ingest(tenant, jnp.asarray(toks), jnp.asarray(labs))
            return jax.block_until_ready(out)
        return f

    def _adapt(self, names, epochs, **kw):
        def f():
            return self.rt.adapt(
                names, epochs=epochs,
                batch_per_tenant=int(self.ft["batch_per_tenant"]),
                key=self.adapt_key, **kw,
            )
        return f

    def _release(self, names):
        def f():
            for t in names:
                self.rt.release(t)
                self.rt.pool.evict(t)
        return f

    def _run_cohort(self, check: bool = False) -> dict:
        """One whole cohort outside the window. With ``check`` its adapt
        runs as one epoch, a snapshot, then the remaining epochs: the same
        per-epoch program and the same trajectory as one call."""
        jobs, pieces = self._cohort_pieces()
        names = [j.tenant for j in jobs]
        snap = None
        for name, fn in pieces:
            if name == "adapt" and check:
                first = self.rt.adapt(
                    names, epochs=1,
                    batch_per_tenant=int(self.ft["batch_per_tenant"]),
                    key=self.adapt_key,
                )
                snap = {
                    "losses": first["losses"],
                    "partitions": [self.rt.tenant(t).partition for t in names],
                    "state": [self._tenant_state(t) for t in names],
                }
                if int(self.ft["epochs"]) < 2:
                    raise ValueError("the check compares steps of a second epoch")
                rest = self.rt.adapt(
                    names, epochs=int(self.ft["epochs"]) - 1,
                    batch_per_tenant=int(self.ft["batch_per_tenant"]),
                )
                snap["later_losses"] = rest["losses"]
            else:
                fn()
        return {"jobs": jobs, "snapshot": snap}

    def _tenant_state(self, t) -> dict:
        st = self.rt.tenant(t)
        host = lambda tree: jax.tree.map(np.asarray, tree)
        return {"adapters": host(st.adapters), "mu": host(st.opt_mu),
                "nu": host(st.opt_nu), "step": st.step}

    def free_program(self):
        """Drop the runtime, its scheduler and every device buffer they
        hold; the benchmark's own weights stay. Returns the weights."""
        self.rt = self.sched = None
        for s in self.served:
            s.req = _Finished(s.req)
        return self.params

    # -- the window ----------------------------------------------------------

    def _submit(self, planned: gen.Planned, due_at: float) -> None:
        s = Served(planned, due_at, submitted_at=time.perf_counter())
        s.req = self.spans("submit", self.rt.enqueue_serve, planned.tenant,
                           planned.prompt, max_new=planned.max_new)
        self.served.append(s)
        self._live.append(s)

    def _stamp(self, now: float, in_window: bool) -> dict:
        """After a step returns: first/last-token stamps and the host's
        token count. Returns what the step delivered: rows, tokens, the
        distinct adapter slots (tenants, None = base) of those rows, and
        the sum of those rows' live depths (prompt and tokens so far)."""
        rows, tokens, slots, depth = 0, 0, set(), 0
        for s in self._live:
            n = len(s.req.tokens)
            if n > s.seen:
                rows += 1
                tokens += n - s.seen
                slots.add(s.planned.tenant)
                depth += s.planned.prompt.size + n
                if in_window:
                    if s.win_lo is None:
                        s.win_lo = s.seen
                    s.win_hi = n
                if s.first_at is None:
                    s.first_at = now
                s.seen = n
            if s.req.done:
                s.last_at = now
        self._live = [s for s in self._live if not s.done]
        if in_window:
            self.tokens_in_window += tokens
        return {"rows": rows, "tokens": tokens, "slots": len(slots),
                "depth": depth}

    def _step(self, in_window: bool) -> None:
        admits0 = self._admits()
        t0 = time.perf_counter()
        self.spans("step", self.sched.step)
        now = time.perf_counter()
        got = self._stamp(now, in_window)
        if in_window:
            got.update(s=now - t0, admits=self._admits() - admits0)
            self.step_log.append(got)

    def _admits(self) -> int:
        c = self.sched.counters
        return c["dispatch/admit"] + c["dispatch/admit_reuse"]

    def run(self, seconds: float, *, drain_limit: float = 60.0) -> None:
        """The measured window, then (open loop) the drain of every request
        due in it. Nothing is submitted after the window closes."""
        self._live: list[Served] = []
        pieces: list = []
        t_start = time.perf_counter()
        t_end = t_start + seconds
        planned = list(self.stream.planned) if self.stream and self.stream.open else []
        nxt = 0
        if self.stream is not None and not self.stream.open:
            for _ in range(self.stream.outstanding):
                self._submit(self.stream.next(), t_start)
        cohort_jobs = None
        annotation = (jax.profiler.TraceAnnotation("bench.window")
                      if self.spans.annotate else None)
        if annotation is not None:
            annotation.__enter__()
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if self.stream is not None and self.stream.open:
                while nxt < len(planned) and t_start + planned[nxt].due <= now:
                    self._submit(planned[nxt], t_start + planned[nxt].due)
                    nxt += 1
            busy = False
            if self._live:
                self._step(True)
                busy = True
                if self.stream is not None and not self.stream.open:
                    t_now = time.perf_counter()
                    while len(self._live) < self.stream.outstanding and t_now < t_end:
                        self._submit(self.stream.next(), t_now)
            if self.ft is not None and time.perf_counter() < t_end:
                if not pieces:
                    cohort_jobs, pieces = self._cohort_pieces()
                name, fn = pieces.pop(0)
                out = self.spans(name, fn)
                busy = True
                if name == "adapt":
                    self.jobs_done.append({
                        "jobs": cohort_jobs, "end": time.perf_counter(),
                        "losses": out["losses"],
                    })
            if not busy:
                wait = (t_start + planned[nxt].due if nxt < len(planned) else t_end)
                self.spans("idle", time.sleep, max(0.0, min(wait, t_end) - time.perf_counter()))
        if annotation is not None:
            annotation.__exit__(None, None, None)
        self.window = (t_start, t_end)
        if self.sched is not None and self.stream.open:
            limit = time.perf_counter() + drain_limit
            while self._live and time.perf_counter() < limit:
                self._step(False)
