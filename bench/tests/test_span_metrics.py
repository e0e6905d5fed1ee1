"""The per-layer metrics that read the program's spans and stamps, on a
hand-made span log: each sum checked by hand, None where the ring lost
spans of the window, and None, with no error, on a program that has no
span ring or first-token stamp."""

from types import SimpleNamespace as NS

import pytest

from bench import run as BR
from repro.core import spans as SP

READERS = ["sched.host_share.tpot", "sched.host_share.tokens",
           "adapt.host_share.finetune", "admit.first_token_ms.ttft"]


def reader(name):
    return BR.metric_reader(BR.ROOT, name)


def ring(*recs, dropped=0, lost_until=float("-inf")):
    r = SP.Recorder()
    for i, (name, parent, a, b) in enumerate(recs, start=1):
        r._append(SP.Record(i, parent, name, a, b, {}))
    r.dropped, r.lost_until = dropped, lost_until
    return r


def session(spans, **kw):
    kw.setdefault("window", (10.0, 20.0))
    return NS(session=NS(rt=NS(spans=spans), sched=object(), ft={},
                         stream=NS(open=True), served=[], **kw))


# Window [10, 20). Tick 1 (10-12) waits 1 s, tick 2 (15-16) waits 0.25 s;
# a tick that starts before the window does not count, nor does a wait
# of it.
TICKS = [
    ("sched.step", 0, 9.0, 10.5), ("sched.wait", 1, 9.5, 10.4),
    ("sched.step", 0, 10.0, 12.0), ("sched.plan", 3, 10.0, 10.1),
    ("sched.wait", 3, 10.5, 11.5), ("sched.harvest", 3, 11.5, 12.0),
    ("sched.step", 0, 15.0, 16.0), ("sched.wait", 7, 15.5, 15.75),
]


@pytest.mark.parametrize("name", ["sched.host_share.tpot",
                                  "sched.host_share.tokens"])
def test_sched_host_share(name):
    # (2 - 1) + (1 - 0.25) = 1.75 s of 10
    assert reader(name)(session(ring(*TICKS))) == pytest.approx(17.5)


def test_adapt_host_share():
    # adapt 10-14: first launch at 10.5, sync ends 13.8: 0.5 + 0.2 s;
    # adapt 15-19: first launch at 15.25, sync ends 19: 0.25 s.
    log = ring(
        ("adapt", 0, 10.0, 14.0), ("adapt.prepare", 1, 10.0, 10.5),
        ("adapt.launch", 1, 10.5, 10.6), ("adapt.launch", 1, 10.6, 10.7),
        ("adapt.writeback", 1, 10.7, 10.8), ("adapt.sync", 1, 10.8, 13.8),
        ("adapt", 0, 15.0, 19.0), ("adapt.launch", 7, 15.25, 15.5),
        ("adapt.sync", 7, 15.5, 19.0),
    )
    assert reader("adapt.host_share.finetune")(session(log)) == \
        pytest.approx(9.5)


def test_first_token_median():
    def served(due, started, first):
        return NS(due_at=due, req=NS(started_at=started, first_token_at=first))

    run = session(ring())
    run.session.served = [
        served(10.0, 10.1, 10.3),            # 200 ms
        served(11.0, 11.5, 11.6),            # 100 ms
        served(12.0, 12.0, 12.4),            # 400 ms
        served(9.0, 9.0, 12.0),              # due before the window
        served(13.0, None, None),            # never admitted
    ]
    assert reader("admit.first_token_ms.ttft")(run) == pytest.approx(200.0)


@pytest.mark.parametrize("name", READERS[:3])
def test_lost_spans_inside_the_window_read_nothing(name):
    log = ring(*TICKS, ("adapt", 0, 10.0, 14.0), ("adapt.launch", 9, 11, 12),
               ("adapt.sync", 9, 12, 13), dropped=3, lost_until=10.0)
    assert reader(name)(session(log)) is None
    log.lost_until = 9.5                     # lost only before the window
    assert reader(name)(session(log)) is not None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_spans_reads_nothing(name):
    """The parent of this change has no span ring and no first-token
    stamp: every reader returns None there, and raises nothing."""
    run = session(None)
    run.session.rt = NS()
    run.session.served = [NS(due_at=10.0, req=NS(started_at=10.1))]
    assert reader(name)(run) is None
