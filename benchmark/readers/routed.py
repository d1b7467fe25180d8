"""Shares of a peak for a model that routes tokens to experts, counted
with the PROGRAM'S routing counters: the window's ``dispatch`` events
carry, per routed layer and micro-step, the (token, expert) pairs that
fell on held experts (``expert_pairs``) and the held experts that were
hit (``experts_hit``). ``of`` says which share:

- ``flops`` or ``bytes``: one whole decode micro-step, as ``decode_share``
  reads it, with the two counters joined to the ``spec`` the family's
  counts get, so that an expert nobody chose is not counted as read;
- ``experts``: the expert layer's grouped products (``op``, ``per_layer``
  kernels a layer) against their roofline: the work of one layer's pairs
  and hit experts (``moe_decode_flops``, ``moe_decode_bytes``) over the
  mean time the trace gives one layer's kernels inside ``module``.

A program without the counters (an engine that counts no routing) gives
nothing to read."""
from benchmark import flops
from benchmark.readers import decode_share, in_window

COUNTERS = ("expert_pairs", "experts_hit")


def counts_needed(spec):
    if spec["of"] == "experts":
        return ("moe_decode_flops", "moe_decode_bytes")
    return decode_share.counts_needed(spec)


def counters(state):
    """The window's mean of each counter, or None where no block of the
    window carries them."""
    blocks = [e["attrs"] for e in in_window(state)
              if e["name"] == "dispatch"
              and all(c in e["attrs"] for c in COUNTERS)]
    if not blocks:
        return None
    return {c: sum(float(a[c]) for a in blocks) / len(blocks)
            for c in COUNTERS}


def read(state, spec):
    seen = counters(state)
    if seen is None:
        return None
    if spec["of"] != "experts":
        return decode_share.read(state, dict(spec, **seen))
    seconds, calls = state["trace"].op_seconds(spec["op"], spec["module"])
    if not calls:
        return None
    work = [getattr(state["counts"], name)(
        state["sz"], seen["expert_pairs"], seen["experts_hit"], spec)
        for name in counts_needed(spec)]
    share = flops.roofline_share(
        *work, seconds / (calls / spec["per_layer"]), state["peak"])
    return None if share is None else share[0]
