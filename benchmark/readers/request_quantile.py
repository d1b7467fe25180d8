"""A quantile over the window's timed requests of ``to`` - ``from`` (two
stamps of the request table), in ms. With ``missing_is_slowest`` a request
that never reached ``to`` counts from ``from`` to the end of the run, which
is slower than all."""
from benchmark.readers import quantile


def read(state, spec):
    rows = [r for r in state["requests"] if r["timed"] and r.get("due")]
    end = max([state["t_close"]] + [r.get("finished", 0.0) for r in rows])
    sample = []
    for r in rows:
        a, b = r.get(spec["from"]), r.get(spec["to"])
        if a is None:
            continue
        if b is None:
            if not spec.get("missing_is_slowest"):
                continue
            b = end + 1.0
        sample.append((b - a) * 1e3)
    return quantile(sample, spec["q"])
