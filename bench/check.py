"""How ``correct`` is decided: the timed path's outputs against the plain
reference (``bench/reference``), each number beside its limit.

Serving: once the window has closed, a sample drawn from the seed of the
requests the program finished (the longest among them) is run through the
reference with its prompt and served tokens; the number is the widest gap
by which a served token's reference logit lies below the reference's best
at that position (``serve_gap``). Every request due in the window must
also have come back whole: ``max_new`` tokens, each in the vocabulary.

Fine-tuning: the cohort the set-up ran through the window's own calls is
compared after its first epoch and on its first three steps with a
reference that recomputes the backbone forward instead of reading the
skip cache: each step's loss (``loss_gap``), the Adam first moment after
the first epoch (``moment_gap``) and the adapters' change over it
(``delta_gap``), the last two by the worst leaf (one tenant's A or B of
one layer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import adapt as RA

#: Leaves whose reference value is under this share of the median leaf of
#: their kind are left out of a leaf comparison (round-off moves them).
LEAF_FLOOR = 1e-3


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def whole(session) -> tuple[int, int]:
    """(attempted, failed): requests due in the window (open loop) or
    submitted in it (closed loop); failed = never came back whole within
    the drain, or came back malformed."""
    vocab = session.cfg["vocab_size"]
    open_loop = session.stream.open
    attempted = failed = 0
    for s in session.served:
        if not open_loop and not s.done:
            continue                     # closed loop: cut by the window end
        attempted += 1
        req = s.req
        if not s.done or req.error is not None:
            failed += 1
            continue
        toks = np.asarray(req.tokens[: req.max_new])
        if toks.size != req.max_new or (toks < 0).any() or (toks >= vocab).any():
            failed += 1
    return attempted, failed


def sample_served(session, min_tokens: int) -> list:
    """Finished requests drawn from the seed: the longest, then others in a
    seeded order until ``min_tokens`` served tokens are in the sample."""
    done = [s for s in session.served if s.done and s.req.error is None]
    if not done:
        return []
    rng = np.random.default_rng([session.seed, 7])
    longest = max(range(len(done)), key=lambda i: (done[i].req.max_new, -i))
    order = [longest] + [i for i in rng.permutation(len(done)) if i != longest]
    out, n = [], 0
    for i in order:
        out.append(done[i])
        n += done[i].req.max_new
        if n >= min_tokens:
            break
    return out


def _adapters_of(session, tenant):
    d = session.mcfg
    if tenant is None:
        return (jnp.zeros((d.n_layers, d.d_model, session.rank), jnp.float32),
                jnp.zeros((d.n_layers, session.rank, d.d_model), jnp.float32))
    k = session.served_names.index(tenant)
    return session.served_adapters["A"][k], session.served_adapters["B"][k]


@jax.jit
def _gaps(ref, other, served):
    """Per position: best reference logit less the reference logit of the
    served token, and of the token ``other`` ranks first."""
    best = jnp.max(ref, -1)
    at = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    top = jnp.argmax(other, -1)
    alt = jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
    return best - at, best - alt


def serve_readings(params, session, sample, pad_to: int,
                   control: bool = False) -> dict:
    """Widest served-token gap over the sample (and, with ``control``, the
    widest gap of the tokens the fp8 reference ranks first). The
    reference is the configuration's own (``session.reference``)."""
    reference = session.reference
    widest, widest_ctrl, n_tok = 0.0, 0.0, 0
    for s in sample:
        prompt = np.asarray(s.req.prompt, np.int32)
        served = np.asarray(s.req.tokens[: s.req.max_new], np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        toks = np.zeros(pad_to, np.int32)
        toks[: seq.size] = seq
        a, b = _adapters_of(session, s.planned.tenant)
        lo, hi = prompt.size - 1, prompt.size - 1 + served.size
        ref = reference.sequence_logits(params, session.cfg, toks, a, b)
        ctrl = (reference.sequence_logits(params, session.cfg, toks, a, b,
                                          "fp8") if control else ref)
        g, gc = _gaps(ref[lo:hi], ctrl[lo:hi], jnp.asarray(served))
        widest = max(widest, float(jnp.max(g)))
        widest_ctrl = max(widest_ctrl, float(jnp.max(gc)))
        n_tok += served.size
        del ref, ctrl
    out = {"serve_gap": widest, "sample_requests": len(sample),
           "sample_tokens": n_tok}
    if control:
        out["control_serve_gap"] = widest_ctrl
    return out


# ---------------------------------------------------------------------------
# Fine-tuning
# ---------------------------------------------------------------------------


def leaf_gap(prog: dict, ref: dict) -> float:
    """Worst leaf of ``|norm(prog leaf) - norm(ref leaf)|`` over the larger
    of the reference leaf's norm and its kind's median leaf norm. A leaf is
    (tenant, A or B, layer); leaves under ``LEAF_FLOOR`` of their kind's
    median in the reference are left out."""
    worst = 0.0
    for kind in ("A", "B"):
        p = np.asarray(prog[kind], np.float64)
        r = np.asarray(ref[kind], np.float64)
        axes = tuple(range(2, p.ndim))
        pn = np.sqrt(np.sum(p * p, axis=axes))          # (tenants, layers)
        rn = np.sqrt(np.sum(r * r, axis=axes))
        med = float(np.median(rn))
        if med <= 0:
            continue
        keep = rn >= LEAF_FLOOR * med
        gap = np.abs(pn - rn) / np.maximum(rn, med)
        if keep.any():
            worst = max(worst, float(np.max(gap[keep])))
    return worst


def job_reference(params, session, precision: str = "f32",
                  keep_rows: float = 1.0) -> list:
    """The reference's first three steps of the check cohort. ``keep_rows``
    below 1 plants the half-batch fault: each step's mean over the first
    share of every tenant's rows only."""
    snap = session.check_job["snapshot"]
    jobs = session.check_job["jobs"]
    ft = session.ft
    tokens = np.concatenate([np.concatenate([b[0] for b in j.batches]) for j in jobs])
    labels = np.stack([np.concatenate([b[1] for b in j.batches]) for j in jobs])
    h, xs = RA.features(session.reference, params, session.cfg, tokens,
                        precision)
    n = int(ft["samples"])
    h = h.reshape((len(jobs), n) + h.shape[1:])
    xs = xs.reshape((len(jobs), n) + xs.shape[1:])
    bpt = int(ft["batch_per_tenant"])
    plan = np.concatenate([
        RA.plan_rows(session.rt_seed, snap["partitions"], e, n, bpt)
        for e in range(2)
    ])
    if keep_rows < 1.0:
        plan = plan[:, :, : max(1, int(bpt * keep_rows))]
    d = session.mcfg
    ad0 = RA.init_adapters(session.adapt_key, len(jobs), d.n_layers, d.d_model,
                           session.rank)
    steps = RA.run_steps(session.reference, params, session.cfg, h, xs,
                         jnp.asarray(labels), plan, ad0,
                         float(session.cfg["adapt_lr"]), 3, precision)
    return [ad0] + steps


def job_readings(session, ref: list, prog=None) -> dict:
    """Compare the program's check cohort (or ``prog``, a reference run in
    its place) with the float32 reference ``ref`` from ``job_reference``."""
    ad0 = ref[0]
    steps = ref[1:]
    if prog is None:
        snap = session.check_job["snapshot"]
        names = [j.tenant for j in session.check_job["jobs"]]
        p_loss = np.stack([
            np.concatenate([snap["losses"][t].reshape(-1),
                            snap["later_losses"][t].reshape(-1)])[:3]
            for t in names
        ], axis=1)                                        # (3, tenants)
        st = snap["state"]
        p_ad = {k: np.stack([s["adapters"][k] for s in st]) for k in ("A", "B")}
        p_mu = {k: np.stack([s["mu"][k] for s in st]) for k in ("A", "B")}
    else:
        p_loss = np.stack([np.asarray(s["losses"]) for s in prog[1:4]])
        p_ad = jax.tree.map(np.asarray, prog[2]["adapters"])
        p_mu = jax.tree.map(np.asarray, prog[2]["mu"])
    r_loss = np.stack([np.asarray(s["losses"]) for s in steps[:3]])
    loss_gap = float(np.max(np.abs(p_loss - r_loss) / np.abs(r_loss)))
    a0 = jax.tree.map(np.asarray, ad0)
    r_ad = jax.tree.map(np.asarray, steps[1]["adapters"])
    delta_p = {k: p_ad[k] - a0[k] for k in ("A", "B")}
    delta_r = {k: r_ad[k] - a0[k] for k in ("A", "B")}
    r_mu = jax.tree.map(np.asarray, steps[1]["mu"])
    return {
        "loss_gap": loss_gap,
        "moment_gap": leaf_gap(p_mu, r_mu),
        "delta_gap": leaf_gap(delta_p, delta_r),
    }
