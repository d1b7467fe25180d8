"""From a profiler trace (``.xplane.pb``) to intervals and sums: the part of
the yardstick that reads the device.

A device plane (``/device:TPU:n``) has a line of programs (``XLA Modules``:
one event for every executed jitted program, ``jit_decode_block(<hash>)``)
and a line of operations (``XLA Ops``: every HLO operation and kernel that
ran, a loop's body inside its ``while``). Host planes carry
the ``TraceAnnotation`` spans of the benchmark's loop (``bench.engine_step``)
and of the program (``serve.admit``, ``serve.prefill``, ``serve.decode``) on
the same clock. Read with nothing but JAX.

Busy time is the union of the operations' intervals on a chip, the window
runs from the first operation's start to the last one's end over the chips
used, and every idle gap between two operations is attributed to the
innermost host span that covers its middle.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"(\.\d+)+$")
#: operations that only contain others: counted by what runs inside them
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """The trace names an operation by its whole HLO line,
    ``%attn.232 = bf16[320,1,64]{...} custom-call(...)``: keep ``attn.232``.
    A Pallas kernel is named after the module scope that calls it."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: an operation's name without the
    numbering the compiler gives it."""
    return _SUFFIX.sub("", op_name(name)) or name


def union_ns(intervals: list) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    total, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def gaps_ns(intervals: list) -> list:
    """The idle ``(start, end)`` gaps between ``(start, end)`` intervals."""
    out, reach = [], None
    for start, end in sorted(intervals):
        if reach is not None and start > reach:
            out.append((reach, start))
        reach = end if reach is None else max(reach, end)
    return out


class Trace:
    """``ops`` and ``modules``: chip -> list of (start_ns, end_ns, name).
    ``spans``: host spans (start_ns, end_ns, name), any thread."""

    def __init__(self, ops: dict, modules: dict, spans: list):
        self.ops, self.modules, self.spans = ops, modules, spans
        chips = [c for c in ops if ops[c]]
        starts = [min(e[0] for e in ops[c]) for c in chips]
        ends = [max(e[1] for e in ops[c]) for c in chips]
        self.window = (min(starts), max(ends)) if chips else (0, 0)
        self.window_s = (self.window[1] - self.window[0]) / 1e9
        busy = [union_ns([(s, e) for s, e, _ in ops[c]]) for c in chips]
        self.busy_by_chip = {c: b / 1e9 for c, b in zip(chips, busy)}
        self.busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0

    # -- sums ---------------------------------------------------------------

    def idle_share(self) -> float | None:
        """1 - busy over the window, on the fullest (busiest) chip."""
        if not self.busy_by_chip or not self.window_s:
            return None
        return 1.0 - max(self.busy_by_chip.values()) / self.window_s

    def _chip(self, chip: int | None) -> int:
        """The first chip that the trace holds, unless one is asked for."""
        return min(self.ops, default=0) if chip is None else chip

    def module_events(self, pattern: str, chip: int | None = None) -> list:
        rx = re.compile(pattern)
        chip = self._chip(chip)
        return sorted(e for e in self.modules.get(chip, ()) if rx.search(e[2]))

    def op_seconds(self, op_pattern: str, module_pattern: str | None = None,
                   chip: int | None = None) -> tuple[float, int]:
        """Summed duration and count of the operations whose name matches,
        inside the programs whose name matches (all programs if None), on
        one chip (the first)."""
        rx = re.compile(op_pattern)
        chip = self._chip(chip)
        inside = None
        if module_pattern is not None:
            mods = self.module_events(module_pattern, chip)
            starts = [m[0] for m in mods]
            inside = (mods, starts)
        total, count = 0, 0
        for start, end, name in self.ops.get(chip, ()):
            if not rx.search(name):
                continue
            if inside is not None:
                i = bisect.bisect_right(inside[1], start) - 1
                if i < 0 or start >= inside[0][i][1]:
                    continue
            total += end - start
            count += 1
        return total / 1e9, count

    def top_ops(self, n: int = 10) -> list:
        """The operation families that took most device time, all chips'
        mean."""
        sums: collections.Counter = collections.Counter()
        for events in self.ops.values():
            for start, end, name in events:
                if op_family(name) not in CONTAINERS:
                    sums[op_family(name)] += end - start
        chips = max(len(self.ops), 1)
        return [[name, ns / chips / 1e9] for name, ns in sums.most_common(n)]

    def idle_gaps(self, chip: int | None = None) -> list:
        """Every idle gap of one chip as (seconds, the host spans that
        cover its middle, outermost first, joined by ``/``). One sweep
        over gaps and spans, both in time order."""
        chip = self._chip(chip)
        gaps = gaps_ns([(s, e) for s, e, _ in self.ops.get(chip, ())])
        spans = sorted(self.spans, key=lambda sp: (sp[0], -sp[1]))
        out, open_spans, nxt = [], [], 0
        for start, end in gaps:
            mid = (start + end) // 2
            while nxt < len(spans) and spans[nxt][0] <= mid:
                open_spans.append(spans[nxt])
                nxt += 1
            open_spans = [sp for sp in open_spans if sp[1] > mid]
            name = "/".join(sp[2] for sp in open_spans) or "(no span)"
            out.append(((end - start) / 1e9, name))
        return out

    def breakdown(self) -> dict:
        gaps = sorted(self.idle_gaps(), reverse=True)
        totals: collections.Counter = collections.Counter()
        for seconds, name in gaps:
            totals[name] += seconds
        listed = [[name, s] for s, name in gaps[:5]]
        listed += [[f"total:{name}", s] for name, s in totals.most_common(5)]
        return {"device_ops": self.top_ops(10), "idle_gaps": listed}


def read_xplane(path: str, chips: int) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chip >= chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] = [(e.start_ns, e.start_ns + e.duration_ns,
                                  op_name(e.name)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[chip] = [(e.start_ns, e.start_ns + e.duration_ns,
                                      e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("bench.", "serve.", "train.")):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return Trace(ops, modules, spans)


def load(trace_dir: str, chips: int) -> Trace:
    """The newest trace under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return read_xplane(paths[-1], chips)
