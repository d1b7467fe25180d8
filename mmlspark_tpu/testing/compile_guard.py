"""Compile-count guard: assert a jitted program's cache stays bounded
across a block of work.

The serving engine's whole design rests on compile-count invariants —
the fused decode BLOCK compiles at most once per power-of-two ladder
size (``decode_compile_count`` counts DISTINCT XLA programs, never scan
iterations), and bucketed prefill compiles at most once per length
bucket (docs/SERVING.md). Those invariants used to be asserted ad hoc
at the end of individual tests; this context manager makes them
reusable and makes the failure mode loud and specific::

    with compile_guard(lambda: engine.decode_compile_count,
                       max_programs=engine.num_decode_blocks,
                       min_programs=1, label="decode"):
        ... drive traffic ...

or, pinning both serve programs to the engine's own ceilings at once::

    with serve_compile_guard(engine):
        ... drive traffic ...

Any callable returning a monotonically non-decreasing program count
works — ``ServeEngine.decode_compile_count`` / ``prefill_compile_count``
wrap jax's ``jitted._cache_size()``, and a raw ``f._cache_size`` does
too. The guard checks the DELTA across the block, so engines with prior
traffic can still be guarded for "no NEW programs" (``max_programs=0``).

SHARDED callables need more care: jax's raw ``_cache_size()`` is the
C++ signature cache, which keys on each argument's committed-ness and
:class:`~jax.sharding.NamedSharding` — an arg that merely changed from
"uncommitted host array" to "committed sharded array" registers as a
new entry even though the tracing cache hits and XLA compiles NOTHING.
:class:`ProgramCountingJit` wraps a jitted callable and counts actual
XLA programs instead, cross-checking the signature-cache delta against
the backend-compile events the call really fired — NamedSharding
re-registrations therefore never count as new programs
(``tests/test_serve_sharded.py`` pins a sharded engine's re-tick to
zero new programs through it).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Callable, Iterator


def jit_cache_size(fn) -> int:
    """Compiled-program count of a jitted callable, -1 when the object
    exposes no ``_cache_size`` (not jitted, or a future jax renamed
    it). ONE definition of the counting contract: ``compile_guard``
    callers, ``ServeEngine``'s compile-count properties, and the
    telemetry plane's ``RetraceWatchdog`` all read through it."""
    cache_size = getattr(fn, "_cache_size", None)
    return cache_size() if callable(cache_size) else -1


#: jax's dispatch layer records this monitoring event once per ACTUAL
#: backend (XLA) compilation — the ground truth ProgramCountingJit
#: cross-checks the signature cache against
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_tls = threading.local()
_listener_installed = False
_listener_lock = threading.Lock()
#: every backend compile the process has made since the listener was
#: installed, whichever thread made it (``backend_compiles`` reads it)
_compiles_total = 0


def _install_compile_listener() -> None:
    """Register the process-wide backend-compile listener (once).
    Imported lazily so merely importing this module never drags jax in."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        from jax._src import monitoring

        def _on_event(event: str, duration: float, **_kw) -> None:
            global _compiles_total
            if event != _BACKEND_COMPILE_EVENT:
                return
            with _listener_lock:
                _compiles_total += 1
            owner = getattr(_tls, "owner", None)
            if owner is not None:
                owner._events += 1

        monitoring.register_event_duration_secs_listener(_on_event)
        _listener_installed = True


def backend_compiles() -> int:
    """Backend (XLA) compiles the whole process has made since the first
    call of this function: the difference between two readings counts
    what compiled in between, jitted families and eager operations
    alike (``SpanTracer.region`` stamps it on a host interval)."""
    if not _listener_installed:
        _install_compile_listener()
    return _compiles_total


class ProgramCountingJit:
    """Wrap a jitted callable so ``_cache_size()`` counts DISTINCT XLA
    programs, sharding-robustly.

    A new program requires BOTH (a) a miss in jax's C++ signature cache
    (the raw ``_cache_size()`` grew) AND (b) at least one backend
    compilation actually firing during the call — so per call the
    program count grows by ``min(signature_delta, compile_events)``.
    Either signal alone overcounts: the signature cache re-registers
    args whose NamedSharding/committed-ness changed without compiling
    anything, and one warm-up call can fire auxiliary compile events
    (e.g. interpret-mode Pallas sub-programs) beyond its one top-level
    program. The wrapper is what ``ServeEngine`` hands its
    ``RetraceWatchdog``s, so ``decode_compile_count`` /
    ``prefill_compile_count`` and every ``compile_guard`` pin read
    true program counts on sharded and unsharded engines alike.

    Attribution is thread-local (compilation is synchronous inside the
    call), so concurrent jits on other threads never cross-count.
    """

    def __init__(self, fn: Callable):
        _install_compile_listener()
        self._fn = fn
        self._programs = 0
        self._events = 0
        self._raw_seen = max(0, jit_cache_size(fn))

    def _cache_size(self) -> int:
        """The jitted-callable counting contract (`jit_cache_size`):
        distinct XLA programs this wrapper has observed compile."""
        return self._programs

    def __call__(self, *args, **kwargs):
        prev_owner = getattr(_tls, "owner", None)
        prev_events = self._events
        _tls.owner = self
        try:
            out = self._fn(*args, **kwargs)
        finally:
            _tls.owner = prev_owner
        raw = max(0, jit_cache_size(self._fn))
        raw_delta = raw - self._raw_seen
        self._raw_seen = raw
        self._programs += max(0, min(raw_delta, self._events - prev_events))
        return out


@contextmanager
def compile_guard(count_fn: Callable[[], int], *, max_programs: int,
                  min_programs: int = 0,
                  label: str = "jitted program") -> Iterator[None]:
    """Assert that at most ``max_programs`` (and at least
    ``min_programs``) NEW programs compile inside the block.

    ``count_fn`` is sampled on entry and exit; the delta is what is
    asserted, as a plain ``AssertionError`` so pytest renders it like
    any inline assert. Exceptions from the block propagate untouched —
    a failing body should fail as itself, not as a compile-count
    message.
    """
    if max_programs < min_programs:
        raise ValueError(
            f"max_programs ({max_programs}) < min_programs "
            f"({min_programs})"
        )
    before = count_fn()
    yield
    grown = count_fn() - before
    if grown > max_programs:
        raise AssertionError(
            f"{label}: {grown} programs compiled, expected at most "
            f"{max_programs} — a shape or static argument is varying "
            "across calls that the design says must share one program"
        )
    if grown < min_programs:
        raise AssertionError(
            f"{label}: {grown} programs compiled, expected at least "
            f"{min_programs} — the guarded block never reached the "
            "jitted path it was meant to exercise"
        )


@contextmanager
def serve_compile_guard(engine, *, min_decode: int = 0,
                        min_prefill: int = 0,
                        label: str = "serve") -> Iterator[None]:
    """Pin BOTH of a ``ServeEngine``'s jitted programs to their design
    ceilings across the block: the fused decode block to its
    power-of-two ladder (``num_decode_blocks`` distinct programs — one
    per scan length T actually run, NOT one per scan iteration) and
    bucketed prefill to ``num_prefill_buckets``. The one-line spelling
    of the serving compile contract for tests that drive traffic."""
    with compile_guard(
        lambda: engine.decode_compile_count,
        max_programs=engine.num_decode_blocks,
        min_programs=min_decode, label=f"{label}.decode",
    ), compile_guard(
        lambda: engine.prefill_compile_count,
        max_programs=engine.num_prefill_buckets,
        min_programs=min_prefill, label=f"{label}.prefill",
    ):
        yield
