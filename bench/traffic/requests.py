"""Serve driver: requests with unique random prompts, open or closed loop.

Parameters (the mix's ``serve`` part):

  loop            ``"open"``: ``rate_per_s`` arrivals over the window,
                  exponential gaps; ``"closed"``: ``outstanding`` requests
                  always in flight (``"max_batch"`` for the scheduler's
                  batch), drawn from a ``pool`` of planned requests
  prompt_len,     length distributions (``bench/gen.quantiles``);
  output_len      ``first_wave_output_len`` replaces the closed loop's
                  first ``outstanding`` outputs, so rows finish staggered
  tenants,        adapted tenants with Zipf(``zipf_s``) popularity, plus
  zipf_s,         ``base_share`` of the requests on the base model
  base_share
  order_seed      optional: the arrival gaps and the lengths are ordered by
                  this fixed seed, so every run seed gets the same arrivals
                  and sizes in the same order (tenants and tokens still
                  come from the run seed); without it the run seed orders
                  them too
  bursts          optional ``{"factor", "seconds", "every"}``: the open
                  loop's arrival rate is ``factor`` times higher for
                  ``seconds`` at the start of every ``every`` seconds, at
                  the same mean ``rate_per_s``
  shared_prefix   optional ``{"len": <distribution>}``: every tenant (and
                  the base model) has one system prompt of its own, put
                  before each of its requests' unique prompt
"""

from __future__ import annotations

import numpy as np

from bench import gen


def burst_times(load: np.ndarray, seconds: float, bursts: dict) -> np.ndarray:
    """Map arrival times spread at a constant rate over ``[0, seconds)`` to
    the same number of arrivals under the burst profile: the cumulative
    intensity is piecewise linear, so its inverse is exact by
    interpolation."""
    f, length, every = (float(bursts[k]) for k in ("factor", "seconds", "every"))
    edges = {0.0, seconds}
    t = 0.0
    while t < seconds:
        edges.update(x for x in (t, t + length) if x < seconds)
        t += every
    pts = np.array(sorted(edges))
    mid = (pts[:-1] + pts[1:]) / 2
    rate = np.where(np.mod(mid, every) < length, f, 1.0)
    cum = np.concatenate([[0.0], np.cumsum(rate * np.diff(pts))])
    return np.interp(load * cum[-1] / seconds, cum, pts)


class Stream:
    def __init__(self, serve: dict, vocab: int, seed: int, seconds: float,
                 max_batch: int):
        self.serve, self.vocab = serve, vocab
        self.rng = np.random.default_rng([seed, 1])
        order = (np.random.default_rng([int(serve["order_seed"]), 1])
                 if "order_seed" in serve else self.rng)
        self.tenants = gen.tenant_names(int(serve["tenants"]))
        self.open = serve["loop"] == "open"
        if self.open:
            n = max(1, int(round(float(serve["rate_per_s"]) * seconds)))
        else:
            out = serve["outstanding"]
            self.outstanding = max_batch if out == "max_batch" else int(out)
            # Enough for the longest window at the shortest output.
            n = int(serve.get("pool", 8192))
        prompts = gen.quantiles(serve["prompt_len"], n)[order.permutation(n)]
        outputs = gen.quantiles(serve["output_len"], n)[order.permutation(n)]
        tenants = gen.tenant_draws(serve, n, self.rng)
        if self.open:
            # Exponential gaps at stratified quantiles, permuted, scaled so
            # the n arrivals fill [0, seconds).
            u = (np.arange(n) + 0.5) / n
            gaps = -np.log1p(-u)[order.permutation(n)]
            due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            due *= seconds / gaps.sum()
            if "bursts" in serve:
                due = burst_times(due, seconds, serve["bursts"])
        else:
            due = np.zeros(n)
        first = serve.get("first_wave_output_len")
        if first is not None and not self.open:
            m = self.outstanding
            outputs[:m] = gen.quantiles(first, m)[order.permutation(m)]
        self.planned = [
            gen.Planned(float(d), t, self._tokens(int(p)), int(o))
            for d, t, p, o in zip(due, tenants, prompts, outputs)
        ]
        shared = serve.get("shared_prefix")
        if shared is not None:
            who = [None] + self.tenants
            k = len(who)
            lens = gen.quantiles(shared["len"], k)[self.rng.permutation(k)]
            prefix = {t: self._tokens(int(n)) for t, n in zip(who, lens)}
            for r in self.planned:
                r.prompt = np.concatenate([prefix[r.tenant], r.prompt])
        self._next = 0

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, n, dtype=np.int32)

    def next(self) -> gen.Planned:
        if self._next >= len(self.planned):
            raise RuntimeError("closed-loop request pool exhausted; raise 'pool'")
        req = self.planned[self._next]
        self._next += 1
        return req


def stream(serve: dict, *, vocab: int, seed: int, seconds: float,
           max_batch: int) -> Stream:
    return Stream(serve, vocab, seed, seconds, max_batch)
