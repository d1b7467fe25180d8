"""The mean over the window's events named ``event`` of the number that
``match`` captures in their attribute ``attr``."""
import re

from benchmark.readers import in_window


def read(state, spec):
    rx = re.compile(spec["match"])
    found = [rx.search(str(e["attrs"].get(spec["attr"], "")))
             for e in in_window(state) if e["name"] == spec["event"]]
    values = [float(m.group(1)) for m in found if m]
    return sum(values) / len(values) if values else None
