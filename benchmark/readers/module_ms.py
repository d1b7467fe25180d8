"""Device time of the programs whose name matches ``module``, in ms.
``per``: ``program`` (the median duration), ``micro_step`` (all their time
over all the decode micro-steps they ran, counted in the trace by the
kernel ``op``), ``start_to_start`` (the median from one program's first
operation to the next one's)."""
from benchmark.readers import median, micro_step_seconds


def read(state, spec):
    if spec["per"] == "micro_step":
        seconds = micro_step_seconds(state, spec)
        return None if seconds is None else seconds * 1e3
    mods = state["trace"].module_events(spec["module"])
    if spec["per"] == "program":
        return median([(e - s) / 1e6 for s, e, _ in mods])
    return median([(b[0] - a[0]) / 1e6 for a, b in zip(mods, mods[1:])])
