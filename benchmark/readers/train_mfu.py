"""Model FLOPs of the tokens the window's steps trained, over the window
and the peak of all the chips: 6 x the weights that multiply a token plus
its share of the causal attention, recomputation not counted. The count is
the family's own (``train_flops_per_token``)."""
from benchmark.readers import train_rate


def counts_needed(spec):
    return ("train_flops_per_token",)


def read(state, spec):
    rate = train_rate.read(state, spec)
    per_token = state["counts"].train_flops_per_token(
        state["sz"], state["seq"], spec)
    return 100.0 * rate * per_token / (
        state["chips"] * state["peak"]["flops_per_s"])
