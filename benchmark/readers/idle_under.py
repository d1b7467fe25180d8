"""The first chip's idle time that lies under matching host spans, as a
percentage of the traced window. Every idle gap carries the path of the
spans that cover its middle, outermost first; ``innermost`` is matched
against the last span of the path, ``any`` against each of them. None
where the trace holds no span that matches at all: a program without
the region has nothing to read."""
import re


def read(state, spec):
    trace = state["trace"]
    rx = re.compile(spec.get("innermost") or spec["any"])
    if not trace.window_s or not any(rx.search(sp[2]) for sp in trace.spans):
        return None
    idle = 0.0
    for seconds, path in trace.idle_gaps():
        names = path.split("/")
        if any(rx.search(n) for n in
               (names[-1:] if "innermost" in spec else names)):
            idle += seconds
    return 100.0 * idle / trace.window_s
