"""What set-up is made of, and what compiled when.

:class:`CompileLog` is ``chip_smoke.py``'s, copied: ``jax.monitoring``
listeners for backend compiles (or persistent-cache fetches) and for the
cache's hits and misses, each stamped on the monotonic clock, plus the
time JAX spent tracing and lowering. :class:`SetupClock` divides
``setup_s`` into named phases.
"""

from __future__ import annotations

import time

#: jax.monitoring duration events -> the name this log keeps them under
_DURATIONS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch_s",
}


class CompileLog:
    """Every backend compile (or persistent-cache retrieval) of the
    process, with the cache's own hit and miss events."""

    def __init__(self):
        import jax.monitoring

        self.compiles: list[tuple[float, float]] = []  # (monotonic t, secs)
        self.seconds = {name: 0.0 for name in _DURATIONS.values()}
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        name = _DURATIONS.get(event)
        if name is None:
            return
        self.seconds[name] += secs
        if name == "backend_compile_s":
            self.compiles.append((time.monotonic(), secs))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def between(self, t0: float, t1: float) -> int:
        """Compiles (or cache fetches) that ended inside ``(t0, t1]``."""
        return sum(1 for t, _ in self.compiles if t0 < t <= t1)

    def snapshot(self) -> dict:
        return {**{k: round(v, 3) for k, v in self.seconds.items()},
                "compile_events": len(self.compiles),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class SetupClock:
    """Named phases of set-up, end to end: ``mark(name)`` closes the phase
    that ran since the last mark. ``start`` is the process's start on the
    monotonic clock."""

    def __init__(self, start: float):
        self.start = start
        self._last = start
        self.phases: dict[str, float] = {}

    def mark(self, name: str, at: float | None = None) -> None:
        now = time.monotonic() if at is None else at
        self.phases[name] = self.phases.get(name, 0.0) + now - self._last
        self._last = now

    def division(self, log: CompileLog | None = None) -> dict:
        out = {f"{k}_s": round(v, 3) for k, v in self.phases.items()}
        if log is not None:
            out["of_which"] = log.snapshot()
        return out


def process_start() -> float:
    """The instant this process started, on ``time.monotonic()``'s clock,
    from ``/proc``: interpreter start-up and imports count in set-up. Where
    ``/proc`` cannot say, now."""
    now = time.monotonic()
    try:
        import os

        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < 600.0:
            return now - age
    except (OSError, ValueError, IndexError):
        pass
    return now
