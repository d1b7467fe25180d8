"""The ``q``-quantile (the median unless ``q`` is given) of ``ms`` over
the window's recorder events named ``region``: one event for every time
the program passed through that host interval
(``SpanTracer.region``), profiler on or off."""
from benchmark.readers import in_window, quantile


def read(state, spec):
    return quantile([e["attrs"]["ms"] for e in in_window(state)
                     if e["name"] == spec["region"]
                     and "ms" in e.get("attrs", {})],
                    spec.get("q", 0.5))
