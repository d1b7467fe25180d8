"""The seeded generators: every seed sends the same multiset of lengths and
of gaps, in the order the file fixes and with token ids of its own;
arrivals lie on the wall clock, and a request is timed from when it was
due."""

import collections

import numpy as np
import pytest

from benchmark import traffic
from benchmark.readers import request_quantile

SEEDS = (3, 2**31 + 12345)
MIXES = ("chat-backlog", "doc-steady")


def lengths_of(reqs):
    return (collections.Counter(len(r.prompt) for r in reqs),
            collections.Counter(r.max_new for r in reqs))


def requests(name, seed, seconds=45.0):
    mix = traffic.load_mix(name)
    if mix["kind"] == "backlog":
        return traffic.backlog_requests(mix, 50257, seed)
    return traffic.open_loop_requests(mix, 50257, seed, seconds)


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_send_the_same_lengths_with_other_tokens(name):
    a, b = (requests(name, s) for s in SEEDS)
    assert lengths_of(a) == lengths_of(b)
    assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    again = requests(name, SEEDS[0])
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
    # the file's order_seed is what orders them: another gives another order
    mix = dict(traffic.load_mix(name), order_seed=7, count=96)
    other = (traffic.backlog_requests(mix, 50257, SEEDS[0])
             if mix["kind"] == "backlog" else
             traffic.open_loop_requests(mix, 50257, SEEDS[0], 45.0))
    assert lengths_of(other) == lengths_of(a)
    assert [len(r.prompt) for r in other] != [len(r.prompt) for r in a]


@pytest.mark.parametrize("name", MIXES)
def test_every_group_of_requests_carries_the_whole_distribution(name):
    mix = traffic.load_mix(name)
    reqs = [r for r in requests(name, SEEDS[0]) if r.timed]
    group = int(mix["group"])
    whole = np.mean([len(r.prompt) for r in reqs])
    for lo in range(0, len(reqs) - group + 1, group):
        part = np.mean([len(r.prompt) for r in reqs[lo:lo + group]])
        assert abs(part - whole) / whole < 0.08


@pytest.mark.parametrize("name", MIXES)
def test_set_up_can_warm_every_length_the_mix_sends(name):
    mix = traffic.load_mix(name)
    warmed = set(traffic.prompt_lengths(mix))
    for seed in SEEDS:
        assert {len(r.prompt) for r in requests(name, seed, 51.0)} <= warmed
    assert len(warmed) <= 32


def test_open_loop_gaps_are_one_multiset_on_the_wall_clock():
    mix = traffic.load_mix("doc-steady")
    seconds, fill = 45.0, float(mix["fill_s"])
    tables = []
    for seed in SEEDS:
        reqs = traffic.open_loop_requests(mix, 50257, seed, seconds)
        timed = [r for r in reqs if r.timed]
        due = np.array([r.due for r in timed])
        assert all(r.due < fill for r in reqs if not r.timed)
        assert due.min() >= fill - 1e-9 and due.max() < fill + seconds
        assert np.all(np.diff(due) >= 0)
        # the file's rate, to the nearest whole group
        assert abs(len(timed) - mix["rate_per_s"] * seconds) <= mix["group"]
        gaps = np.diff(np.append(due, fill + seconds))
        assert gaps.sum() == pytest.approx(seconds - (due[0] - fill))
        tables.append(np.sort(np.round(gaps, 9)))
    assert np.array_equal(tables[0], tables[1])


def test_exponential_gaps_are_bursty_not_even():
    mix = traffic.load_mix("doc-steady")
    gaps = traffic.strata(dict(mix["gap"], mean=1.0), 120, 12).reshape(-1)
    assert gaps.std() / gaps.mean() > 0.8   # an even schedule would read 0


def test_first_slotful_is_staggered():
    mix = traffic.load_mix("chat-backlog")
    reqs = traffic.backlog_requests(mix, 50257, 5)
    cuts = traffic.first_slotful_budgets(reqs, 24)
    shares = sorted(c / r.max_new for c, r in zip(cuts, reqs))
    assert len(cuts) == 24 and cuts[-1] == reqs[23].max_new
    assert np.allclose(shares, (np.arange(24) + 1) / 24, atol=0.02)


def test_training_rows_all_differ_and_follow_the_seed():
    x, y = traffic.train_batches(50257, 9, 12, 64)
    assert x.shape == y.shape == (12, 64)
    assert np.array_equal(x[:, 1:], y[:, :-1])
    assert len({row.tobytes() for row in x}) == 12
    assert not np.array_equal(x, traffic.train_batches(50257, 10, 12, 64)[0])


def test_a_request_is_timed_from_when_it_was_due():
    rows = [{"timed": True, "due": 10.0, "sent": 10.3, "first_token": 10.5},
            {"timed": True, "due": 11.0, "sent": 11.0, "first_token": 11.2},
            {"timed": True, "due": 12.0, "sent": 12.1},          # never came
            {"timed": False, "due": 1.0, "sent": 1.0, "first_token": 9.0}]
    state = {"requests": rows, "t_close": 20.0}
    spec = {"from": "due", "to": "first_token", "missing_is_slowest": True}
    assert request_quantile.read(state, dict(spec, q=0.5)) == pytest.approx(500)
    # the one that never came is slower than all, not left out
    assert request_quantile.read(state, dict(spec, q=0.95)) > 8000
    late = {"from": "due", "to": "sent", "q": 0.99}
    assert request_quantile.read(state, late) == pytest.approx(300)


def test_an_unknown_distribution_or_kind_is_refused(tmp_path):
    with pytest.raises(traffic.TrafficError):
        traffic.quantile({"dist": "zipf"}, np.array([0.5]))
    with pytest.raises(traffic.TrafficError):
        traffic.strata({"dist": "uniform", "lo": 1, "hi": 2}, 10, 4)
