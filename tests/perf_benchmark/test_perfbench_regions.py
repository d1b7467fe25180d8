"""The two readers of the program's regions, by hand: ``region_quantile``
over the recorder's events of the window and ``idle_under`` over the idle
gaps of a hand-made trace; and every metric file that names a region names
one the program has."""

import json
import os
import re

import pytest

from benchmark import trace_reduce
from benchmark.readers import event_mean, idle_under, region_quantile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "..", "..")
MS = 1_000_000


def region(name, t, ms, **attrs):
    return {"t": t, "name": name, "attrs": {"t0": t - ms / 1e3, "ms": ms,
                                            **attrs}}


def state_of(events, trace=None):
    return {"events": events, "t_open": 10.0, "t_close": 20.0,
            "trace": trace}


def test_region_quantile_reads_the_windows_events_of_one_name():
    events = [region("serve.admit_one", 9.0, 900.0),        # before the window
              *(region("serve.admit_one", 11.0 + i, ms, request=i)
                for i, ms in enumerate([210.0, 190.0, 400.0, 205.0, 195.0])),
              region("serve.pool_write", 12.0, 150.0, dispatches=146),
              region("serve.admit_one", 21.0, 1.0),          # after it
              {"t": 12.5, "name": "serve.admit_one"},        # no interval
              {"t": 13.0, "name": "admitted", "span": 3,
               "span_name": "request", "attrs": {"slot": 1}}]
    spec = {"region": "serve.admit_one", "q": 0.5}
    assert region_quantile.read(state_of(events), spec) == 205.0
    assert region_quantile.read(state_of(events), dict(spec, q=0.0)) == 190.0
    assert region_quantile.read(state_of(events), dict(spec, q=0.99)) == 400.0
    assert region_quantile.read(
        state_of(events), {"region": "serve.pool_write"}) == 150.0
    # a program without the region, or a window without it: nothing
    assert region_quantile.read(state_of(events),
                                {"region": "train.feed"}) is None
    assert region_quantile.read(state_of([]), spec) is None
    # the counts that a region carries go through the accepted reader
    counts = {"event": "serve.pool_write", "attr": "dispatches",
              "match": r"(\d+)"}
    assert event_mean.read(state_of(events), counts) == 146.0
    assert event_mean.read(state_of(events[:2]), counts) is None


def admit_trace():
    """10 ms on one chip: programs at 0-1, 3-4, 6-7 and 9-10 ms, so three
    idle gaps of 2 ms, in the middle of a pool write, of the wait for the
    first token, and of the retiring that follows the fetch."""
    ops = {0: [(0, 1 * MS, "fusion.1"), (3 * MS, 4 * MS, "fusion.2"),
               (6 * MS, 7 * MS, "fusion.3"), (9 * MS, 10 * MS, "fusion.4")]}
    spans = [(0, 10 * MS, "bench.engine_step"),
             (0, 10 * MS, "serve.tick"),
             (int(0.5 * MS), int(5.9 * MS), "serve.admit"),
             (int(0.6 * MS), int(5.8 * MS), "serve.admit_one"),
             (int(0.7 * MS), int(5.7 * MS), "serve.prefill"),
             (int(1.5 * MS), int(2.6 * MS), "serve.pool_write"),
             (int(4.2 * MS), int(5.6 * MS), "serve.first_token"),
             (int(7.5 * MS), int(9.5 * MS), "serve.retire")]
    return trace_reduce.Trace(ops, {}, spans)


def test_idle_under_sums_the_gaps_whose_spans_match():
    state = state_of([], admit_trace())
    paths = [p for _, p in state["trace"].idle_gaps()]
    assert paths == [
        "bench.engine_step/serve.tick/serve.admit/serve.admit_one/"
        "serve.prefill/serve.pool_write",
        "bench.engine_step/serve.tick/serve.admit/serve.admit_one/"
        "serve.prefill/serve.first_token",
        "bench.engine_step/serve.tick/serve.retire"]
    # 2 of the window's 10 ms lie under each leaf, 4 under the admit loop
    assert idle_under.read(state, {"any": r"^serve\.admit$"}) == \
        pytest.approx(40.0)
    assert idle_under.read(
        state, {"innermost": r"^serve\.pool_write$"}) == pytest.approx(20.0)
    assert idle_under.read(
        state, {"innermost": r"^serve\.(pool_write|first_token)$"}
    ) == pytest.approx(40.0)
    assert idle_under.read(state, {"any": r"^serve\.tick$"}) == \
        pytest.approx(60.0)
    # an outer span is not the innermost of a gap that a leaf covers
    assert idle_under.read(
        state, {"innermost": r"^serve\.admit$"}) == pytest.approx(0.0)


def test_idle_under_reads_nothing_where_no_span_matches():
    """The parent of the PR that brought a region has no such span: the
    metric is left out there, it is not nought."""
    state = state_of([], admit_trace())
    assert idle_under.read(state, {"any": r"^train\.step$"}) is None
    empty = trace_reduce.Trace({}, {}, [(0, MS, "serve.admit")])
    assert idle_under.read(state_of([], empty),
                           {"any": r"^serve\.admit$"}) is None


def metric_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    out = []
    for name in names:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        if spec["reader"] in ("region_quantile", "idle_under") or (
                spec["reader"] == "event_mean" and "." in spec["event"]):
            out.append((name, spec))
    return out


@pytest.mark.parametrize("name,spec", metric_files(),
                         ids=[n for n, _ in metric_files()])
def test_a_metric_that_reads_a_region_names_one_the_program_has(name, spec):
    sources = ""
    for part in ("serve/engine.py", "train/trainer.py"):
        with open(os.path.join(ROOT, "mmlspark_tpu", part)) as f:
            sources += f.read()
    marked = set(re.findall(r'region\(\s*"((?:serve|train)\.\w+)"', sources))
    pattern = (spec.get("region") or spec.get("event")
               or spec.get("innermost") or spec.get("any"))
    if spec["reader"] == "idle_under":
        assert any(re.search(pattern, region) for region in marked), pattern
    else:
        assert pattern in marked, pattern
