"""The time a collective (``op``) runs on a chip while no other operation
does, over the traced window, on the first chip."""
import re

from benchmark.trace_reduce import union_ns


def read(state, spec):
    trace = state["trace"]
    rx = re.compile(spec["op"])
    events = trace.ops.get(min(trace.ops, default=0), ())
    coll = [(s, e) for s, e, n in events if rx.search(n)]
    rest = [(s, e) for s, e, n in events if not rx.search(n)]
    if not coll or not trace.window_s:
        return None
    exposed = union_ns(coll + rest) - union_ns(rest)
    return 100.0 * exposed / 1e9 / trace.window_s
