"""The attention kernel's share of its roofline: the least time the chip
could take for one call's work (true lengths, stored widths) over the mean
time the trace gives a call of the kernel (``op``) inside the programs
``module``. ``phase`` says whose work: ``decode`` (the window's mean
micro-step), ``prefill`` (the window's mean prompt), ``train`` (one chip's
rows; forward and both backward kernels together). The count is the
family's own (``attn_<phase>_flops``, ``attn_<phase>_bytes``)."""
from benchmark import flops
from benchmark.readers import decode_blocks
from benchmark.readers.decode_share import micro_steps


def counts_needed(spec):
    return (f"attn_{spec['phase']}_flops", f"attn_{spec['phase']}_bytes")


def read(state, spec):
    seconds, calls = state["trace"].op_seconds(spec["op"], spec["module"])
    if not calls:
        return None
    sz = state["sz"]
    ops, nbytes = (getattr(state["counts"], n) for n in counts_needed(spec))
    if spec["phase"] == "decode":
        work = [(ops(sz, l, spec), nbytes(sz, l, spec))
                for l in micro_steps(decode_blocks(state))]
        per_call = seconds / calls
    elif spec["phase"] == "prefill":
        lens = [len(r["prompt"]) for r in state["requests"]
                if state["t_open"] < r.get("first_token", 0.0)
                <= state["t_close"]]
        work = [(ops(sz, n, spec), nbytes(sz, n, spec)) for n in lens]
        per_call = seconds / calls
    else:
        rows = state["rows"] // state["chips"]   # one chip's share
        work = [(ops(sz, state["seq"], spec) * rows,
                 nbytes(sz, state["seq"], spec) * rows)]
        per_call = seconds / (calls / 3)         # three kernels a layer
    if not work:
        return None
    f = sum(w[0] for w in work) / len(work)
    b = sum(w[1] for w in work) / len(work)
    share = flops.roofline_share(f, b, per_call, state["peak"])
    return None if share is None else share[0]
