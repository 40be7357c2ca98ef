"""The traffic generator: one seed gives one schedule, seeds differ only in
literals (arrival times and orders are the same), and the drill window
repeats sessions only as its Zipf counts say."""
import benchpath  # noqa: F401
import pytest

from benchpath import MIXES, small_config


def _schedule(config, mix, seed, seconds=30.0):
    from lib import traffic
    from lib.data import generate

    data = generate(small_config(config), 1)
    return traffic.schedule(mix, data, seed, seconds)


def _sqls(s):
    return [r.sql for r in s.requests] + [r.sql for d in s.dashboards for r in d]


@pytest.mark.parametrize("config,mix", MIXES)
def test_same_seed_same_schedule(config, mix):
    a, b = _schedule(config, mix, 2**31 + 5), _schedule(config, mix, 2**31 + 5)
    assert _sqls(a) == _sqls(b)
    assert [r.due for r in a.requests] == [r.due for r in b.requests]
    assert [r.sql for w in a.warmup for r in w] == [r.sql for w in b.warmup for r in w]


@pytest.mark.parametrize("config,mix", MIXES)
def test_seeds_change_literals_not_amount(config, mix):
    a, b = _schedule(config, mix, 3), _schedule(config, mix, 4)
    assert _sqls(a) != _sqls(b)
    assert len(a.requests) == len(b.requests)
    assert len(a.dashboards) == len(b.dashboards)
    # the same arrivals, and the same session steps or shapes at each
    assert [r.due for r in a.requests] == [r.due for r in b.requests]
    assert [r.kind for r in a.requests] == [r.kind for r in b.requests]


def test_open_loop_arrivals_fill_the_window():
    from lib import traffic

    s = _schedule("ssb-sf10", "ssb_adhoc", 9, seconds=30.0)
    mix = traffic.load("ssb_adhoc")
    due = [r.due for r in s.requests]
    assert len(due) == round(mix["rate_per_s"] * 30.0)
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30.0


def test_drill_repeats_only_what_zipf_gives():
    from lib import traffic

    mix = traffic.load("ssb_drill")
    s = _schedule("ssb-sf10", "ssb_drill", 11, seconds=60.0)
    steps = len(mix["steps"])
    sessions = {}
    for r in s.requests:
        sessions.setdefault(r.group, []).append(r)
    # a session is one spec: one filter for all its steps
    specs = {}
    for sid, reqs in sessions.items():
        filters = {repr(r.intent["filters"]) for r in reqs}
        assert len(filters) == 1
        assert len(reqs) <= steps
        specs[sid] = (reqs[0].kind.split(":")[0], filters.pop())
    n_sessions = len(specs)
    n_ranks = len(mix["hierarchies"]) * len(mix["years"]) * len(mix["regions"])
    slots = mix["sessions_open"]
    total = slots * -(-(-(-len(s.requests) // slots)) // steps)
    counts = traffic.zipf_counts(n_ranks, total, mix["zipf_s"])
    seen = {}
    for spec in specs.values():
        seen[spec] = seen.get(spec, 0) + 1
    # every spec recurs no more often than its rank's count allows
    assert max(seen.values()) <= counts.max()
    assert len(seen) <= int((counts > 0).sum())
    assert n_sessions <= total
