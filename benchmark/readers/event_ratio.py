"""Over the window's events named ``event``: the sum of their attribute
``part`` as a percentage of the sum of their attribute ``whole``. Events
that lack either (a program that does not count them) are left out."""
from benchmark.readers import in_window


def read(state, spec):
    pairs = [(e["attrs"][spec["part"]], e["attrs"][spec["whole"]])
             for e in in_window(state) if e["name"] == spec["event"]
             and spec["part"] in e["attrs"] and spec["whole"] in e["attrs"]]
    whole = sum(float(w) for _, w in pairs)
    if not whole:
        return None
    return 100.0 * sum(float(p) for p, _ in pairs) / whole
