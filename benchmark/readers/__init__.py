"""Readers: each takes one kind of number from a run's state (recorder
events, the request table, the compile log, the device trace) as a metric's
file under ``benchmark/metrics/`` asks. ``read(state, spec)`` returns the
number, or None where it finds nothing to read."""

from __future__ import annotations

import statistics


def in_window(state: dict) -> list:
    """The recorder's events stamped inside the window."""
    return [e for e in state["events"]
            if state["t_open"] < e["t"] <= state["t_close"]]


def quantile(values: list, q: float):
    """The q-quantile, by the nearest rank from below of the sorted
    sample; None of nothing."""
    if not values:
        return None
    values = sorted(values)
    return values[min(len(values) - 1, int(q * len(values)))]


def median(values: list):
    return statistics.median(values) if values else None


def decode_blocks(state: dict) -> list:
    """Per decode block of the window: its scan length ``T`` and, per slot,
    (frontier before the block, tokens it took)."""
    ticks: dict = {}
    for e in in_window(state):
        if e["name"] == "decode" and e.get("span_name") == "request":
            a = e["attrs"]
            block = ticks.setdefault(e["tick"], {"T": a["block"], "slots": []})
            block["slots"].append((a["pos"], a["tokens"]))
    return list(ticks.values())


def micro_step_seconds(state: dict, spec: dict):
    """Mean device time of one decode micro-step in the traced part: the
    time of the programs ``module`` over the micro-steps they ran, which
    the trace itself counts (the kernel ``op`` runs once a layer in every
    micro-step)."""
    trace = state["trace"]
    mods = trace.module_events(spec["module"])
    _, calls = trace.op_seconds(spec["op"], spec["module"])
    steps = calls / state["sz"]["layers"]
    if not mods or not steps:
        return None
    return sum(e - s for s, e, _ in mods) / 1e9 / steps
