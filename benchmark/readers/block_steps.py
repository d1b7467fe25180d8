"""Shares of a peak for a model that generates by diffusion over blocks
(a ``denoise`` program: ``S`` denoising micro-steps and a clean close a
block, every slot at a block's start when the program starts). The
``request`` span's ``decode`` events give, per slot and program, the
block start ``pos`` and the ``blocks`` it ran; micro-step ``i`` of a
program reads, for each slot still running its block ``i // (S + 1)``,
the slot's clean prefix and that block's ``L`` rows (``sz["block_len"]``,
``sz["denoise_steps"]``). ``of`` says which share:

- ``flops`` or ``bytes``: one whole micro-step (the family's
  ``decode_step_flops`` / ``_bytes``), with the routing counters of the
  window's ``dispatch`` events (``routed.counters``), over the mean device
  time of a micro-step (the kernel ``op`` counted once a layer);
- ``attn``: the attention kernel ``op`` against its roofline: the mean
  micro-step's work of one layer's call (``attn_decode_flops`` /
  ``_bytes``) over the mean time the trace gives a call inside
  ``module``.

A program of one token a time (its events carry no ``blocks``) gives
nothing to read."""
from benchmark import flops
from benchmark.readers import in_window, micro_step_seconds
from benchmark.readers.routed import counters


def counts_needed(spec):
    if spec["of"] == "attn":
        return ("attn_decode_flops", "attn_decode_bytes")
    return (f"decode_step_{spec['of']}",)


def micro_steps(state):
    """The live lengths of every micro-step of every program of the
    window."""
    sz = state["sz"]
    per_block, length = sz["denoise_steps"] + 1, sz["block_len"]
    programs: dict = {}
    for e in in_window(state):
        a = e["attrs"]
        if (e["name"] == "decode" and e.get("span_name") == "request"
                and "blocks" in a):
            program = programs.setdefault(e["tick"], {"T": a["block"],
                                                      "slots": []})
            program["slots"].append((a["pos"], a["blocks"]))
    for program in programs.values():
        for i in range(program["T"]):
            b = i // per_block
            lens = [pos + (b + 1) * length
                    for pos, blocks in program["slots"] if b < blocks]
            if lens:
                yield lens


def read(state, spec):
    steps = list(micro_steps(state))
    seen = counters(state)
    if not steps or seen is None:
        return None
    sz, counts = state["sz"], state["counts"]
    if spec["of"] == "attn":
        seconds, calls = state["trace"].op_seconds(spec["op"],
                                                   spec["module"])
        if not calls:
            return None
        work = [sum(getattr(counts, name)(sz, lens, spec) for lens in steps)
                / len(steps) for name in counts_needed(spec)]
        share = flops.roofline_share(*work, seconds / calls, state["peak"])
        return None if share is None else share[0]
    seconds = micro_step_seconds(state, spec)
    if seconds is None:
        return None
    fn = getattr(counts, counts_needed(spec)[0])
    work = sum(fn(sz, lens, dict(spec, **seen)) for lens in steps) / len(steps)
    rate = state["peak"]["flops_per_s" if spec["of"] == "flops"
                         else "hbm_bytes_per_s"]
    return 100.0 * work / rate / seconds
