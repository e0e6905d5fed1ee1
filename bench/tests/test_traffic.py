"""The request driver's parameters: the same work for every seed, bursts at
the same mean rate, and shared per-tenant prefixes."""

import numpy as np

from bench import gen

REQUESTS = gen.driver("requests")

SERVE = {
    "driver": "requests", "loop": "open", "rate_per_s": 20.0,
    "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.7,
                   "min": 4, "max": 128},
    "output_len": {"dist": "uniform", "min": 4, "max": 16},
    "tenants": 8, "zipf_s": 1.0, "base_share": 0.1,
}


def _stream(serve, seed, seconds=10.0):
    return REQUESTS.stream(serve, vocab=100, seed=seed, seconds=seconds, max_batch=4)


def test_seeds_differ_by_order_only():
    a, b = _stream(SERVE, 2**31 + 5), _stream(SERVE, 7)
    for f in (lambda r: r.prompt.size, lambda r: r.max_new, lambda r: str(r.tenant)):
        assert sorted(map(f, a.planned)) == sorted(map(f, b.planned))
    assert [r.prompt.size for r in a.planned] != [r.prompt.size for r in b.planned]
    assert all(0 <= r.due < 10.0 for r in a.planned)


def test_bursts_keep_the_mean_rate():
    bursty = dict(SERVE, bursts={"factor": 4, "seconds": 2, "every": 5})
    s = _stream(bursty, 11, seconds=20.0)
    due = np.array([r.due for r in s.planned])
    assert due.size == 400 and (np.diff(due) >= 0).all() and due.max() < 20.0
    inside = (np.mod(due, 5.0) < 2.0).sum()
    # Bursts hold 2 s of every 5 at 4x the rate: 8 / (8 + 3) of the arrivals.
    assert abs(inside / due.size - 8 / 11) < 0.03


def test_burst_times_inverts_the_intensity():
    load = np.linspace(0, 10, 1001)[:-1]
    t = REQUESTS.burst_times(load, 10.0, {"factor": 3, "seconds": 1, "every": 4})
    assert t[0] == 0.0 and t[-1] < 10.0 and (np.diff(t) > 0).all()
    # Intensity 3 on [0,1), [4,5), [8,9) and 1 elsewhere: 16 units in all.
    assert np.isclose(np.interp(3.0 * 10 / 16, load, t), 1.0, atol=0.02)


def test_shared_prefix_per_tenant():
    shared = dict(SERVE, shared_prefix={"len": {"dist": "uniform", "min": 5, "max": 9}})
    plain, s = _stream(SERVE, 3), _stream(shared, 3)
    heads = {}
    for p, r in zip(plain.planned, s.planned):
        n = r.prompt.size - p.prompt.size
        assert 5 <= n <= 9
        assert (r.prompt[n:] == p.prompt).all()
        head = heads.setdefault(r.tenant, r.prompt[:n])
        assert head.size == n and (head == r.prompt[:n]).all()
    assert len(heads) > 2


def test_order_seed_fixes_arrivals_and_sizes():
    fixed = dict(SERVE, order_seed=1)
    a, b = _stream(fixed, 2**31 + 5), _stream(fixed, 7)
    for f in (lambda r: r.due, lambda r: r.prompt.size, lambda r: r.max_new):
        assert list(map(f, a.planned)) == list(map(f, b.planned))
    assert any((x.prompt != y.prompt).any() for x, y in zip(a.planned, b.planned))
    assert [r.tenant for r in a.planned] != [r.tenant for r in b.planned]
