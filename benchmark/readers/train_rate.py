"""Tokens of the optimizer steps that ended inside the window (whose two
edges are step ends), over the window, all chips together."""
from benchmark.readers import in_window


def read(state, spec):
    steps = sum(1 for e in in_window(state) if e["name"] == "step")
    return steps * state["tokens_per_step"] / (
        state["t_close"] - state["t_open"])
