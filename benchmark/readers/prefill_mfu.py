"""Operations of the window's mean prompt at its TRUE length (not its
bucket) over the mean device time of a prefill program in the traced part
and the chip's peak. The count is the family's own (``prefill_flops``)."""


def counts_needed(spec):
    return ("prefill_flops",)


def read(state, spec):
    mods = state["trace"].module_events(spec["module"])
    lens = [len(r["prompt"]) for r in state["requests"]
            if state["t_open"] < r.get("first_token", 0.0) <= state["t_close"]]
    if not mods or not lens:
        return None
    seconds = sum(e - s for s, e, _ in mods) / len(mods) / 1e9
    fn = state["counts"].prefill_flops
    work = sum(fn(state["sz"], n, spec) for n in lens) / len(lens)
    return 100.0 * work / state["peak"]["flops_per_s"] / seconds
