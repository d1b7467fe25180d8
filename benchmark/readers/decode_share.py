"""One decode micro-step against the chip's peaks: operations (``of``:
``flops``) or bytes (``of``: ``bytes``) of the window's mean micro-step,
from the live lengths the recorder saw, over the mean device time of a
micro-step in the traced part. Dead slots and rows past a slot's frontier
are not counted, parameters count once at their stored width. The count is
the family's own (``decode_step_flops``, ``decode_step_bytes``)."""
from benchmark.readers import decode_blocks, micro_step_seconds


def counts_needed(spec):
    return (f"decode_step_{spec['of']}",)


def micro_steps(blocks):
    """The live lengths of every micro-step of every block."""
    for b in blocks:
        for i in range(b["T"]):
            lens = [pos + 1 + i for pos, took in b["slots"] if i < took]
            if lens:
                yield lens


def read(state, spec):
    steps = list(micro_steps(decode_blocks(state)))
    seconds = micro_step_seconds(state, spec)
    if not steps or seconds is None:
        return None
    fn = getattr(state["counts"], counts_needed(spec)[0])
    work = sum(fn(state["sz"], lens, spec) for lens in steps) / len(steps)
    rate = state["peak"]["flops_per_s" if spec["of"] == "flops"
                         else "hbm_bytes_per_s"]
    return 100.0 * work / rate / seconds
