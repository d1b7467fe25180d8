"""Deterministic fault injection for the serving AND training
resilience layers.

Large-scale ML systems treat component failure as a design axis, not an
exception: TensorFlow's runtime recovers workers from checkpointed
state and retries rather than restarting the job (arXiv:1605.08695 §4).
To *prove* the serve engine — and the SPMD trainer beside it — has the
same property, failures must be reproducible — a chaos test that cannot
replay its faults cannot bisect a regression. This module is the
seeded, schedulable fault source the engine's hook points
(``serve.prefill``, ``serve.decode``, ``serve.device_get``, the
periodic-checkpoint ``serve.snapshot``), the supervisor's
``serve.health`` probe, and the trainer's ``train.*`` hook points
(``train.step``, ``train.data``, ``train.checkpoint``,
``train.restore`` — docs/TRAINING.md "Failure semantics") fire into
(docs/OBSERVABILITY.md "Fault injection"):

- **Zero overhead when disabled.** The engine holds ``faults=None`` by
  default and every hook is a single ``is not None`` check on the host
  path — no wrapper, no extra dispatch, nothing in the jitted programs
  (the ``serve_faults`` bench group pins the tokens/sec delta to
  noise).
- **Deterministic.** Faults come from an explicit :class:`Fault`
  schedule (fire at site X on tick N for request R, ``times`` firings)
  and/or a seeded rate table (one ``default_rng(seed)`` draw per hook
  firing) — the same seed over the same traffic replays the same fault
  sequence, which is what lets the chaos soak assert exact terminal
  statuses and token parity.
- **Typed.** Injected failures raise :class:`TransientFault` /
  :class:`ResourceExhausted` / :class:`EngineKilled`; the engine's
  classifiers (:func:`is_transient`, :func:`is_resource_exhausted`)
  match the injected types AND the real runtime's ``JaxRuntimeError``
  status spellings, so the same retry/degrade/quarantine policy covers
  simulated and genuine failures.

Fault kinds:

``transient``
    A retryable dispatch error (the injected stand-in for a flaky
    interconnect / preempted core). Raised at the hook, BEFORE the
    jitted call, so donated buffers are never consumed by a failed
    attempt and the engine's capped-backoff retry is always safe.
``oom``
    Simulated ``RESOURCE_EXHAUSTED`` — drives the engine's graceful
    degradation (step down the decode-block ladder, cap admissions,
    preempt + requeue).
``stall``
    Sleeps ``stall_s`` at the hook: a slow tick, visible as a
    ``tick_ms`` outlier, with no error raised.
``poison``
    Corrupts a request's token stream (an out-of-vocab id) via
    :meth:`FaultInjector.poison_value` / :meth:`poison_block`. The
    engine's token validation quarantines exactly the poisoned request.
``kill``
    Raises :class:`EngineKilled` — the simulated process crash for the
    snapshot/restore drill. NOT retried and NOT caught by ``run()``:
    the engine is dead; rebuild it with ``ServeEngine.restore``.
``corrupt``
    Silent data corruption: a seeded single-bit flip on a chosen
    pytree leaf or wire payload (core/integrity.py), decided via
    :meth:`FaultInjector.corrupt_spec`. Like ``poison`` it is a
    VALUE kind — never raised; the call site applies the flip and the
    integrity plane (in-graph audits, payload/snapshot/checkpoint
    checksums) must detect it. Spelled ``site:corrupt=rate`` in
    :func:`parse_fault_spec` specs, e.g.
    ``"seed=7,train.step:corrupt=0.05"``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from mmlspark_tpu.core.exceptions import FriendlyError

#: engine + control-plane hook points a fault can target.
#: ``serve.snapshot`` fires inside the engine's periodic checkpoint —
#: a fault there models a checkpoint that fails MID-WRITE, so the
#: engine must keep the previous complete snapshot (a torn checkpoint
#: is not restorable). ``serve.health`` fires in the supervisor's
#: per-replica probe — a fault there is a failed health check and
#: quarantines + fails over the replica (serve/supervisor.py).
#: ``serve.handoff`` fires when a decode-role engine adopts a
#: cross-replica KV hand-off payload (serve/fleet.py): a fault there
#: models a lost/corrupt hand-off, and the engine falls back to a full
#: local prefill so the request still completes bit-identically.
#: ``serve.batch`` fires before each STATELESS batch dispatch of a
#: multi-model deployment (serve/multimodel.py): transients retry with
#: the same capped deterministic backoff as decode, ``oom`` halves the
#: deployment's batch admission cap (graceful degradation down the
#: batch-bucket ladder — no new programs), and retry exhaustion
#: quarantines the whole batch as ``"failed"``.
#: The four ``train.*`` sites are the SPMD trainer's hook points
#: (train/trainer.py, docs/TRAINING.md): ``train.step`` fires before
#: each optimizer-step dispatch (transients retry with deterministic
#: backoff, ``oom`` walks the gradient-accumulation ladder, ``kill``
#: is the crash the bit-exact-resume drill restores from),
#: ``train.data`` fires before each host batch pull (``poison`` there
#: corrupts the batch with NaNs — the injected stand-in for a bad
#: gradient the anomaly quarantine must skip), ``train.checkpoint``
#: fires between the checkpoint payload write and the manifest commit
#: (a fault models a torn mid-write failure; the previous checkpoint
#: must stay restorable), and ``train.restore`` fires before a resume
#: reads the store.
SITES = (
    "serve.prefill", "serve.decode", "serve.device_get",
    "serve.snapshot", "serve.health", "serve.handoff", "serve.batch",
    "train.step", "train.data", "train.checkpoint", "train.restore",
)
#: fault kinds fire() raises/sleeps for, in rate-table draw order
FIRE_KINDS = ("transient", "oom", "stall", "kill")
#: value kinds — never raised; the call site applies the corruption
#: (``poison`` via poison_value/poison_block, ``corrupt`` via
#: corrupt_spec + core/integrity.py's seeded bit-flip helpers)
KINDS = FIRE_KINDS + ("poison", "corrupt")

#: poison token injected when a Fault does not name its own value —
#: negative, so it is out-of-range for every vocabulary
POISON_TOKEN = -7


class InjectedFault(RuntimeError):
    """Base of every injector-raised failure (never a FriendlyError:
    faults simulate the RUNTIME failing, not the user misusing the
    API)."""


class TransientFault(InjectedFault):
    """A retryable dispatch failure — the engine's capped deterministic
    backoff absorbs up to ``retry_limit`` of these per dispatch."""


class ResourceExhausted(InjectedFault):
    """Simulated allocation failure; the message carries the runtime's
    ``RESOURCE_EXHAUSTED`` spelling so string-matching classifiers see
    injected and real OOMs identically."""

    def __init__(self, message: str = ""):
        super().__init__(
            f"RESOURCE_EXHAUSTED: {message or 'injected allocation failure'}"
        )


class EngineKilled(InjectedFault):
    """Simulated process crash. Escapes ``ServeEngine.run()`` by
    design — recovery is ``ServeEngine.restore(snapshot)``, not a
    retry."""


#: how the TPU compiler words a refusal (a kernel or program that does
#: not fit SMEM/VMEM/HBM *as compiled*). It carries the same
#: RESOURCE_EXHAUSTED status as a runtime allocation failure, but no
#: amount of load shedding makes the program compile: it is a crash
_COMPILE_REFUSAL = "compile permanent error"


def is_resource_exhausted(exc: BaseException) -> bool:
    """True for injected OOMs and for real RUNTIME errors carrying the
    ``RESOURCE_EXHAUSTED`` status (jax surfaces allocation failure as
    ``JaxRuntimeError: RESOURCE_EXHAUSTED: ...``). A compile-time
    refusal is NOT one: it propagates out of ``ServeEngine.step`` /
    ``run`` instead of walking the degradation ladder and quarantining
    requests as if the pool were merely full."""
    if isinstance(exc, ResourceExhausted):
        return True
    msg = str(exc)
    return "RESOURCE_EXHAUSTED" in msg and _COMPILE_REFUSAL not in msg


#: real-runtime statuses safe to retry: the dispatch failed to START,
#: it did not half-execute (RESOURCE_EXHAUSTED is handled separately —
#: retrying without degrading would just OOM again)
_TRANSIENT_STATUSES = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "CANCELLED")


def is_transient(exc: BaseException) -> bool:
    """True for injected transients and for real
    ``jax.errors.JaxRuntimeError``s whose status is a retryable one
    (UNAVAILABLE / DEADLINE_EXCEEDED / CANCELLED)."""
    if isinstance(exc, TransientFault):
        return True
    if isinstance(exc, (ResourceExhausted, EngineKilled)):
        return False
    import jax

    if isinstance(exc, jax.errors.JaxRuntimeError):
        msg = str(exc)
        return any(s in msg for s in _TRANSIENT_STATUSES)
    return False


@dataclass
class Fault:
    """One scheduled fault: fire ``kind`` at ``site``, optionally
    pinned to an engine ``tick`` and/or a ``request`` id (prefill and
    poison targeting) or a ``slot`` (device_get poison targeting);
    ``times`` firings before the entry is spent."""

    site: str
    kind: str
    tick: int | None = None
    request: int | None = None
    slot: int | None = None
    #: pin the fault to ONE replica of a ReplicaSet (the supervisor
    #: tags every engine hook firing with its replica index) — the
    #: replica-targeted ``kill`` the failover drill injects; None
    #: matches any replica AND single-engine (untagged) firings
    replica: int | None = None
    times: int = 1
    value: int = POISON_TOKEN

    def __post_init__(self):
        if self.site not in SITES:
            raise FriendlyError(
                f"unknown fault site {self.site!r}; hook points are "
                f"{SITES}"
            )
        if self.kind not in KINDS:
            raise FriendlyError(
                f"unknown fault kind {self.kind!r}; kinds are {KINDS}"
            )


class FaultInjector:
    """Deterministic fault source for the engine's hook points.

    Two modes, composable: an explicit ``schedule`` of :class:`Fault`
    entries (matched first, most-specific semantics) and a seeded
    ``rates`` table (``{"transient": 0.05, "oom": 0.02, ...}`` — one
    ``default_rng(seed)`` uniform draw per hook firing, walked
    cumulatively in :data:`FIRE_KINDS` order, plus one per-request
    draw for ``poison``). Engine behavior is deterministic given its
    traffic, so the draw sequence — and therefore the whole fault
    replay — is a pure function of ``seed``.

    ``listener(kind, site)`` is called on every injection (the engine
    wires it to its metrics + flight recorder, so every injected fault
    lands in the same ``events.jsonl`` timeline as its consequences).
    """

    def __init__(self, schedule=(), *, seed: int | None = None,
                 rates: dict[str, float] | None = None,
                 site_rates: dict[str, dict[str, float]] | None = None,
                 stall_s: float = 0.001, listener=None):
        self.schedule: list[Fault] = list(schedule)
        self.rates = dict(rates or {})
        for kind, rate in self.rates.items():
            if kind not in KINDS:
                raise FriendlyError(
                    f"unknown fault kind {kind!r} in rates; kinds are "
                    f"{KINDS}"
                )
            if not 0.0 <= float(rate) <= 1.0:
                raise FriendlyError(
                    f"fault rate for {kind!r} must be in [0, 1], got "
                    f"{rate}"
                )
        #: per-site rate OVERRIDES layered on the global table — how a
        #: drill raises pressure on one hook (say the snapshot path)
        #: without also chaos-ing every dispatch
        self.site_rates = {
            site: dict(kinds) for site, kinds in (site_rates or {}).items()
        }
        for site, kinds in self.site_rates.items():
            if site not in SITES:
                raise FriendlyError(
                    f"unknown fault site {site!r} in site_rates; hook "
                    f"points are {SITES}"
                )
            for kind, rate in kinds.items():
                if kind not in KINDS:
                    raise FriendlyError(
                        f"unknown fault kind {kind!r} in site_rates"
                        f"[{site!r}]; kinds are {KINDS}"
                    )
                if not 0.0 <= float(rate) <= 1.0:
                    raise FriendlyError(
                        f"fault rate for {site}:{kind} must be in "
                        f"[0, 1], got {rate}"
                    )
        if (self.rates or self.site_rates) and seed is None:
            raise FriendlyError(
                "rate-based fault injection needs a seed — unseeded "
                "faults cannot be replayed, which defeats the harness"
            )
        self._rng = np.random.default_rng(seed) if seed is not None else None
        self.stall_s = stall_s
        self.listener = listener
        #: kind -> injections so far (the chaos soak's ground truth)
        self.counts: dict[str, int] = {}
        self.injected_total = 0

    # -- bookkeeping -------------------------------------------------------

    def _record(self, kind: str, site: str) -> None:
        self.counts[kind] = self.counts.get(kind, 0) + 1
        self.injected_total += 1
        if self.listener is not None:
            self.listener(kind, site)

    def _take(self, site: str, kinds: tuple, *, tick: int,
              request: int | None, slot: int | None = None,
              replica: int | None = None) -> Fault | None:
        """Pop (decrement) the first matching unspent schedule entry."""
        for f in self.schedule:
            if f.times <= 0 or f.site != site or f.kind not in kinds:
                continue
            if f.tick is not None and f.tick != tick:
                continue
            if (
                f.request is not None
                and request is not None
                and f.request != request
            ):
                continue
            if f.request is not None and request is None:
                continue
            if f.slot is not None and slot is not None and f.slot != slot:
                continue
            # replica targeting: a pinned fault fires ONLY on that
            # replica's tagged hooks — an untagged (single-engine)
            # firing never matches a replica-pinned entry
            if f.replica is not None and f.replica != replica:
                continue
            f.times -= 1
            return f
        return None

    def _rate(self, site: str, kind: str) -> float:
        """Effective rate for one (site, kind): the site override when
        present, else the global table."""
        over = self.site_rates.get(site)
        if over is not None and kind in over:
            return float(over[kind])
        return float(self.rates.get(kind, 0.0))

    def _draw(self, site: str, kinds: tuple) -> str | None:
        """One seeded uniform against the cumulative rate table."""
        if self._rng is None:
            return None
        active = [(k, self._rate(site, k)) for k in kinds]
        if not any(r for _, r in active):
            return None
        u = float(self._rng.random())
        acc = 0.0
        for kind, rate in active:
            acc += rate
            if u < acc:
                return kind
        return None

    # -- the engine-facing surface -----------------------------------------

    def fire(self, site: str, *, tick: int, request: int | None = None,
             replica: int | None = None) -> None:
        """One hook firing: raise/stall per the schedule and rate
        table, or return silently. Called by the engine immediately
        BEFORE the guarded dispatch, so a raised fault never consumes
        donated buffers. ``replica`` is the firing engine's ReplicaSet
        index (None outside a supervisor) — what replica-pinned
        schedule entries match against."""
        f = self._take(site, FIRE_KINDS, tick=tick, request=request,
                       replica=replica)
        kind = f.kind if f is not None else self._draw(site, FIRE_KINDS)
        if kind is None:
            return
        self._record(kind, site)
        if kind == "transient":
            raise TransientFault(
                f"injected transient fault at {site} (tick {tick})"
            )
        if kind == "oom":
            raise ResourceExhausted(f"injected at {site} (tick {tick})")
        if kind == "kill":
            raise EngineKilled(
                f"injected engine kill at {site} (tick {tick})"
            )
        # stall: a slow tick, not an error
        time.sleep(self.stall_s)

    def poison_value(self, site: str, *, tick: int,
                     request: int | None = None,
                     replica: int | None = None) -> int | None:
        """Poison token for one request's scalar token (the prefill
        first-token path), or None."""
        f = self._take(site, ("poison",), tick=tick, request=request,
                       replica=replica)
        if f is not None:
            self._record("poison", site)
            return int(f.value)
        if self._draw(site, ("poison",)) is not None:
            self._record("poison", site)
            return POISON_TOKEN
        return None

    def poison_block(self, site: str, tokens: np.ndarray, *, tick: int,
                     slots: list[int],
                     replica: int | None = None) -> np.ndarray:
        """Poison the fetched ``(S, T)`` decode block: corrupt column 0
        of a targeted (or the lowest, or a seeded-drawn) active slot's
        row. Returns a fresh array; the device state is untouched —
        poison models host-visible corruption of ONE request, which is
        exactly what the engine's quarantine must contain."""
        if not slots:
            return tokens
        hit: list[tuple[int, int]] = []
        for slot in slots:
            f = self._take(site, ("poison",), tick=tick, request=None,
                           slot=slot, replica=replica)
            if f is not None:
                self._record("poison", site)
                hit.append((slot if f.slot is None else f.slot, f.value))
                continue
            if self._draw(site, ("poison",)) is not None:
                self._record("poison", site)
                hit.append((slot, POISON_TOKEN))
        if not hit:
            return tokens
        tokens = np.array(tokens, copy=True)
        for slot, value in hit:
            tokens[slot, 0] = value
        return tokens

    def corrupt_spec(self, site: str, *, tick: int,
                     request: int | None = None,
                     slot: int | None = None,
                     replica: int | None = None) -> int | None:
        """Decide whether this hook firing suffers silent data
        corruption: returns a deterministic bit-flip seed (for
        core/integrity.py's ``flip_bit_*`` / ``corrupt_replica``
        helpers) or None. A scheduled :class:`Fault` whose ``value``
        is set (non-default) pins the seed exactly — how a drill flips
        the SAME bit every replay; otherwise the seed derives from the
        injector's corrupt count, so rate-drawn flips are replayable
        too. The call site applies the flip; this method only decides
        and records."""
        f = self._take(site, ("corrupt",), tick=tick, request=request,
                       slot=slot, replica=replica)
        if f is None and self._draw(site, ("corrupt",)) is None:
            return None
        ordinal = self.counts.get("corrupt", 0)
        self._record("corrupt", site)
        if f is not None and f.value != POISON_TOKEN:
            return int(f.value)
        # derived seed: distinct per injection, identical per replay
        return ordinal * 1_000_003 + 17


def parse_fault_spec(spec: str) -> FaultInjector:
    """CLI/bench spelling -> injector: ``"seed=7,transient=0.05,
    oom=0.02,poison=0.02,stall=0.01,stall_s=0.001"``. Kind keys are
    rates; ``seed`` and ``stall_s`` configure the injector. A key of
    the form ``site:kind`` (``"serve.snapshot:transient=0.5"``) scopes
    the rate to ONE hook site — how a CLI drill pressures the
    checkpoint or health-probe paths without chaos-ing every
    dispatch."""
    seed = None
    stall_s = 0.001
    rates: dict[str, float] = {}
    site_rates: dict[str, dict[str, float]] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise FriendlyError(
                f"bad fault spec entry {part!r}: expected key=value "
                "pairs like 'seed=7,transient=0.05'"
            )
        key, _, value = part.partition("=")
        key = key.strip()
        try:
            if key == "seed":
                seed = int(value)
            elif key == "stall_s":
                stall_s = float(value)
            elif ":" in key:
                site, _, kind = key.partition(":")
                site, kind = site.strip(), kind.strip()
                if site not in SITES:
                    raise FriendlyError(
                        f"unknown fault site {site!r} in spec key "
                        f"{key!r}; hook points are {SITES}"
                    )
                if kind not in KINDS:
                    raise FriendlyError(
                        f"unknown fault kind {kind!r} in spec key "
                        f"{key!r}; kinds are {KINDS}"
                    )
                site_rates.setdefault(site, {})[kind] = float(value)
            elif key in KINDS:
                rates[key] = float(value)
            else:
                raise FriendlyError(
                    f"unknown fault spec key {key!r}; use 'seed', "
                    f"'stall_s', a kind rate from {KINDS}, or a "
                    "site-scoped 'site:kind' rate"
                )
        except ValueError as e:
            raise FriendlyError(
                f"bad fault spec value {value!r} for {key!r}: {e}"
            ) from e
    return FaultInjector(seed=seed, rates=rates, site_rates=site_rates,
                         stall_s=stall_s)
