"""Device-level performance analytics: program cost attribution (MFU,
HBM bandwidth), Chrome/Perfetto trace export, and SLO monitoring.

The telemetry plane (:mod:`mmlspark_tpu.core.telemetry`) sees host
wall-clock: a decode block "took 12 ms". This module turns those
intervals into device-honest figures — was the TPU at 5% or 55% MFU,
is decode actually HBM-bound as the flash_decode design assumes — by
combining XLA's ANALYTIC cost model with the dispatch intervals the
engine already measures at its existing sync points. Three pieces:

- :func:`analyze_jit_cost` + :class:`PerfAnalytics`: at compile time,
  every lowered program family (prefill bucket, decode block T, their
  sharded variants) is lowered once more from abstract
  ``ShapeDtypeStruct`` leaves — tracing only, NO backend compile, no
  device work, no host sync — and ``Lowered.cost_analysis()`` yields
  analytic FLOPs and bytes-accessed. Dividing by the measured dispatch
  interval at the *existing* per-block sync gives per-family ``mfu``
  and ``hbm_bw_util_pct`` against the device's peak
  (:func:`device_peak`), plus a device-vs-host time split — with ZERO
  new host syncs, so the one-``device_get``-per-block contract and the
  ``compile_guard`` program-count pins hold unchanged (asserted in
  ``tests/test_perf.py``). Backends whose cost model returns nothing
  (interpreters) degrade to ``source="unavailable"`` and ``None``
  figures, never an error.
- :func:`export_chrome_trace`: FlightRecorder events + request spans
  -> Chrome trace-event JSON (``trace.json``), loadable in Perfetto
  (ui.perfetto.dev) with one track per request, a tick track, and
  program-dispatch slices. Timestamps anchor to the recorder's
  ``t0_unix`` epoch so traces from different processes correlate.
- :class:`SloMonitor`: declared TTFT / per-token p99 targets and an
  error-rate budget over a rolling window; burning the budget emits
  ``slo_violation`` flight-recorder alerts and raises ``should_shed``,
  which the serve engine's admission control honors (composing with
  the memory-pressure degraded mode, docs/SERVING.md "Failure
  semantics"). Recovery emits ``slo_recovered``. The clock is
  injectable, so the window arithmetic is testable on synthetic time.

All of it is host-side stdlib + lazy jax (docs/OBSERVABILITY.md
"Device-level performance analytics").
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from collections import deque
from typing import Any, Callable

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.logging_utils import get_logger

_log = get_logger("perf")


# --------------------------------------------------------------------------
# device peaks
# --------------------------------------------------------------------------

#: device_kind prefix -> (peak dense bf16 FLOP/s, peak HBM bytes/s) per
#: chip, from the vendor's published specs (v5e: Google Cloud "TPU v5e"
#: documentation, 197 TFLOP/s bf16, 819 GB/s). Matched by longest
#: prefix against ``jax.devices()[0].device_kind``. This is the ONE
#: peak table (bench.py reads it too); a kind that is not in it is an
#: error, not a default. ``cpu`` is the explicit NOMINAL entry — one
#: core's order of magnitude, so CPU test runs still compute ratios;
#: ``peak_source`` labels it and it is never a hardware claim.
DEVICE_PEAKS: dict[str, tuple[float, float]] = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
    "cpu": (5e10, 2e10),
}


@dataclasses.dataclass(frozen=True)
class DevicePeak:
    """Peak FLOP/s and HBM bandwidth one device can sustain, plus where
    the figure came from (``"table"`` for known accelerators,
    ``"nominal"`` for the table's ``cpu`` entry, ``"env"`` for the
    ``MMLTPU_PEAK_FLOPS`` / ``MMLTPU_PEAK_HBM_BYTES_PER_S``
    overrides)."""

    flops_per_s: float
    hbm_bytes_per_s: float
    source: str
    device_kind: str

    def to_dict(self) -> dict:
        return {
            "flops_per_s": self.flops_per_s,
            "hbm_bytes_per_s": self.hbm_bytes_per_s,
            "source": self.source,
            "device_kind": self.device_kind,
        }


def device_peak(device=None) -> DevicePeak:
    """Resolve the peak figures for ``device`` (default: the first jax
    device) from :data:`DEVICE_PEAKS`. Env overrides win, each over its
    own figure. A ``device_kind`` the table does not know raises unless
    BOTH overrides are given: a ratio against a guessed peak is a
    number about another machine."""
    env_flops = os.environ.get("MMLTPU_PEAK_FLOPS")
    env_bw = os.environ.get("MMLTPU_PEAK_HBM_BYTES_PER_S")
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = device.device_kind
    hit = _lookup_peak(kind)
    if hit is None and not (env_flops and env_bw):
        raise FriendlyError(
            f"no peak FLOP/s and HBM bandwidth known for device_kind "
            f"{kind!r}: add it to core/perf.py DEVICE_PEAKS with its "
            "source, or set both MMLTPU_PEAK_FLOPS and "
            "MMLTPU_PEAK_HBM_BYTES_PER_S"
        )
    if env_flops or env_bw:
        return DevicePeak(
            float(env_flops) if env_flops else hit[0],
            float(env_bw) if env_bw else hit[1],
            "env", kind,
        )
    return DevicePeak(*hit, "nominal" if kind == "cpu" else "table", kind)


def _lookup_peak(kind: str) -> tuple[float, float] | None:
    best = None
    for prefix, peaks in DEVICE_PEAKS.items():
        if kind.startswith(prefix) and (
            best is None or len(prefix) > len(best[0])
        ):
            best = (prefix, peaks)
    return best[1] if best else None


# --------------------------------------------------------------------------
# program cost analysis
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProgramCost:
    """Analytic cost of ONE lowered XLA program: total FLOPs and bytes
    accessed per execution, from ``Lowered.cost_analysis()``.
    ``source`` is ``"xla"`` when the cost model answered and
    ``"unavailable"`` on backends where it returns nothing (the
    interpreter fallback path) — figures are then ``None`` and every
    derived ratio (MFU, bandwidth) follows suit instead of erroring.
    ``kernel_calls`` counts the Pallas TPU kernels (``tpu_custom_call``)
    in the lowered program: 0 means the kernels' interpreter or a dense
    path was traced instead, which is how ``chip_smoke.py`` tells that
    the program a phase ran is the one the chip is meant to run."""

    flops: float | None
    bytes_accessed: float | None
    source: str = "xla"
    kernel_calls: int | None = None

    @classmethod
    def unavailable(cls) -> "ProgramCost":
        return cls(None, None, "unavailable")

    def to_dict(self) -> dict:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "source": self.source,
        }


def _as_abstract(leaf):
    """Array-like leaves -> ShapeDtypeStruct; everything else (static
    ints, None) passes through. Holding no buffers means the lowering
    below can never touch donated device memory. A committed array's
    sharding is kept, so the program lowered for analysis is the one
    dispatched (dropping it under a mesh lowers a program whose
    donations cannot match the pinned out_shardings, and warns)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return leaf
    import jax

    sharding = getattr(leaf, "sharding", None)
    if not isinstance(sharding, jax.sharding.Sharding):
        sharding = None
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def analyze_jit_cost(jitted, *args, **kwargs) -> ProgramCost:
    """Lower ``jitted`` at the abstract signature of ``args`` and run
    XLA's analytic cost model.

    This is TRACING only: no backend compile (so
    ``testing/compile_guard.py`` counts and ``RetraceWatchdog`` budgets
    are untouched — lowering fires no backend-compile monitoring
    event), no device work, no host sync. Arrays are converted to
    ``ShapeDtypeStruct`` first, so donated buffers are never
    referenced. Any failure — a backend whose cost model returns
    nothing, a tracing error — degrades to
    :meth:`ProgramCost.unavailable`, never an exception: analytics must
    not be able to take the serving path down."""
    try:
        import jax

        a, kw = jax.tree_util.tree_map(_as_abstract, (args, kwargs))
        lowered = jitted.lower(*a, **kw)
        kernels = lowered.as_text().count("tpu_custom_call")
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        flops = ca.get("flops") if ca else None
        bts = ca.get("bytes accessed") if ca else None
        if flops is None and bts is None:
            return ProgramCost(None, None, "unavailable", kernels)
        return ProgramCost(
            float(flops) if flops is not None else None,
            float(bts) if bts is not None else None,
            "xla", kernels,
        )
    except Exception as e:  # noqa: BLE001 — analytics must never raise
        _log.info("cost analysis unavailable: %s", e)
        return ProgramCost.unavailable()


# --------------------------------------------------------------------------
# per-family dispatch attribution
# --------------------------------------------------------------------------


@dataclasses.dataclass
class _FamilyStats:
    cost: ProgramCost
    dispatches: int = 0
    device_s: float = 0.0
    #: issue-to-start time under a pipelined host loop: the span a
    #: dispatch spent QUEUED behind the previous block's in-flight
    #: execution — excluded from device_s so MFU/BW stay honest
    queued_s: float = 0.0
    tokens: int = 0


class PerfAnalytics:
    """Per-program-family MFU / bandwidth attribution and the
    device-vs-host time split.

    The serve engine registers each program family ONCE (``ensure`` /
    ``register_program``) with its analytic :class:`ProgramCost`, then
    reports every dispatch's measured interval — the wall time between
    issuing the program and the block's one existing host sync
    completing — via :meth:`record_dispatch`. No new syncs, no device
    round-trips: everything here is host arithmetic over numbers the
    engine already had. Per-family and overall gauges
    (``perf.mfu``, ``perf.hbm_bw_util_pct``, ``perf.device_time_pct``)
    land in the shared registry; :meth:`summary` is the JSON view
    ``ServeMetrics.to_dict()`` embeds (schema-gated)."""

    def __init__(self, *, registry=None, n_devices: int = 1,
                 peak: DevicePeak | None = None, enabled: bool = True):
        self.enabled = enabled
        self.n_devices = max(1, int(n_devices))
        self._peak: DevicePeak | None = peak
        self._families: dict[str, _FamilyStats] = {}
        self._tick_s = 0.0
        self._registry = registry

    @property
    def peak(self) -> DevicePeak:
        # resolved lazily: construction must not force a jax backend
        if self._peak is None:
            self._peak = device_peak()
        return self._peak

    def wants_program(self, family: str) -> bool:
        """True when ``family`` has not been analyzed yet (and the
        plane is enabled) — the engine's one-branch guard before paying
        the once-per-family lowering."""
        return self.enabled and family not in self._families

    def register_program(self, family: str, cost: ProgramCost) -> None:
        if family in self._families:
            return
        self._families[family] = _FamilyStats(cost=cost)
        _log.info(
            "perf: program family %s registered (flops=%s bytes=%s "
            "source=%s)", family, cost.flops, cost.bytes_accessed,
            cost.source,
        )

    def ensure(self, family: str,
               analyze: Callable[[], ProgramCost]) -> None:
        """Register ``family`` via ``analyze()`` on first sight; no-op
        (zero work beyond one dict probe) afterwards."""
        if self.wants_program(family):
            self.register_program(family, analyze())

    def record_dispatch(self, family: str, seconds: float,
                        tokens: int = 0, queued_s: float = 0.0) -> None:
        """One dispatched execution of ``family`` that took ``seconds``
        measured at the block's EXISTING sync point. ``queued_s`` is
        the portion of that interval the dispatch spent queued behind a
        still-executing previous block (the async host loop's
        pipelining): it is real wall time but NOT device execution, so
        it is excluded from the device_s the MFU/BW denominators use —
        without the split, a perfectly pipelined engine would halve its
        apparent MFU while doing exactly the same math."""
        if not self.enabled:
            return
        st = self._families.get(family)
        if st is None:
            # dispatch observed before/without registration (analytics
            # partially disabled): still attribute the time
            st = _FamilyStats(cost=ProgramCost.unavailable())
            self._families[family] = st
        st.dispatches += 1
        queued_s = min(max(0.0, queued_s), max(0.0, seconds))
        st.device_s += seconds - queued_s
        st.queued_s += queued_s
        st.tokens += tokens
        if self._registry is not None:
            g = self._registry.gauge(f"perf.{family}.mfu")
            mfu = self._family_mfu(st)
            if mfu is not None:
                g.set(mfu)
            bw = self._family_bw_pct(st)
            if bw is not None:
                self._registry.gauge(
                    f"perf.{family}.hbm_bw_util_pct"
                ).set(bw)
            overall = self.overall()
            if overall["mfu"] is not None:
                self._registry.gauge("perf.mfu").set(overall["mfu"])
            if overall["hbm_bw_util_pct"] is not None:
                self._registry.gauge("perf.hbm_bw_util_pct").set(
                    overall["hbm_bw_util_pct"]
                )

    def record_tick(self, seconds: float) -> None:
        """One engine tick's total wall time — the denominator of the
        device-vs-host split."""
        if self.enabled:
            self._tick_s += seconds
            if self._registry is not None:
                pct = self.device_time_pct()
                if pct is not None:
                    self._registry.gauge("perf.device_time_pct").set(pct)

    # -- derived figures ---------------------------------------------------

    def _family_mfu(self, st: _FamilyStats) -> float | None:
        if st.cost.flops is None or st.device_s <= 0:
            return None
        achieved = st.cost.flops * st.dispatches / st.device_s
        return achieved / (self.peak.flops_per_s * self.n_devices)

    def _family_bw_pct(self, st: _FamilyStats) -> float | None:
        if st.cost.bytes_accessed is None or st.device_s <= 0:
            return None
        achieved = st.cost.bytes_accessed * st.dispatches / st.device_s
        return 100.0 * achieved / (
            self.peak.hbm_bytes_per_s * self.n_devices
        )

    def device_seconds(self) -> float:
        return sum(st.device_s for st in self._families.values())

    def host_seconds(self) -> float:
        """Tick wall time NOT inside a device dispatch interval:
        scheduling, admission bookkeeping, span/metric recording."""
        return max(0.0, self._tick_s - self.device_seconds())

    def device_time_pct(self) -> float | None:
        if self._tick_s <= 0:
            return None
        return 100.0 * min(1.0, self.device_seconds() / self._tick_s)

    def overall(self) -> dict:
        """Dispatch-weighted MFU / bandwidth over every family with an
        analyzed cost; ``None`` while nothing analyzable ran."""
        flops = bts = 0.0
        flops_s = bytes_s = 0.0
        for st in self._families.values():
            if st.device_s <= 0:
                continue
            if st.cost.flops is not None:
                flops += st.cost.flops * st.dispatches
                flops_s += st.device_s
            if st.cost.bytes_accessed is not None:
                bts += st.cost.bytes_accessed * st.dispatches
                bytes_s += st.device_s
        mfu = (
            flops / flops_s / (self.peak.flops_per_s * self.n_devices)
            if flops_s > 0 else None
        )
        bw = (
            100.0 * bts / bytes_s
            / (self.peak.hbm_bytes_per_s * self.n_devices)
            if bytes_s > 0 else None
        )
        return {"mfu": mfu, "hbm_bw_util_pct": bw}

    def summary(self) -> dict:
        """The JSON-able analytics view ``ServeMetrics.to_dict()``
        embeds (and ``tools/check_metrics_schema.py`` gates)."""
        overall = self.overall()
        fams = {}
        for family in sorted(self._families):
            st = self._families[family]
            fams[family] = {
                "flops": st.cost.flops,
                "bytes_accessed": st.cost.bytes_accessed,
                "cost_source": st.cost.source,
                "kernel_calls": st.cost.kernel_calls,
                "dispatches": st.dispatches,
                "device_s": round(st.device_s, 6),
                "queued_s": round(st.queued_s, 6),
                "tokens": st.tokens,
                "mfu": _rnd(self._family_mfu(st), 6),
                "hbm_bw_util_pct": _rnd(self._family_bw_pct(st), 4),
            }
        return {
            "mfu": _rnd(overall["mfu"], 6),
            "hbm_bw_util_pct": _rnd(overall["hbm_bw_util_pct"], 4),
            "device_time_s": round(self.device_seconds(), 6),
            "host_time_s": round(self.host_seconds(), 6),
            "device_time_pct": _rnd(self.device_time_pct(), 4),
            "families": fams,
            "peak": {**self.peak.to_dict(), "devices": self.n_devices},
        }


def _rnd(value: float | None, digits: int) -> float | None:
    return round(value, digits) if value is not None else None


# --------------------------------------------------------------------------
# SLO monitor
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SloTargets:
    """Declared service-level objectives over a rolling window.
    ``None`` targets are not monitored; ``error_rate`` is the budgeted
    fraction of non-``completed`` terminal statuses."""

    ttft_p99_ms: float | None = None
    per_token_p99_ms: float | None = None
    error_rate: float | None = None
    window_s: float = 60.0
    #: a signal needs at least this many window samples before it can
    #: violate — one slow warm-up request must not trip a p99 alert
    min_samples: int = 5

    def declared(self) -> bool:
        return any(
            t is not None
            for t in (self.ttft_p99_ms, self.per_token_p99_ms,
                      self.error_rate)
        )

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def parse_slo_spec(spec: str) -> SloTargets:
    """CLI spelling -> :class:`SloTargets`:
    ``"ttft_p99_ms=50,per_token_p99_ms=5,error_rate=0.05,window_s=30"``.
    Unknown keys raise the typed error with the valid vocabulary."""
    fields = {f.name for f in dataclasses.fields(SloTargets)}
    out: dict[str, float] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise FriendlyError(
                f"bad SLO spec item {part!r}: expected key=value "
                f"(keys: {sorted(fields)})"
            )
        key, _, val = part.partition("=")
        key = key.strip()
        if key not in fields:
            raise FriendlyError(
                f"unknown SLO key {key!r} (keys: {sorted(fields)})"
            )
        try:
            out[key] = (
                int(val) if key == "min_samples" else float(val)
            )
        except ValueError:
            raise FriendlyError(
                f"SLO key {key!r} needs a number, got {val!r}"
            ) from None
    targets = SloTargets(**out)
    if not targets.declared():
        raise FriendlyError(
            "SLO spec declares no target: set at least one of "
            "ttft_p99_ms, per_token_p99_ms, error_rate"
        )
    return targets


def _p99(values: list[float]) -> float:
    """Exact p99 over the window samples (nearest-rank) — small windows
    deserve exactness, and exactness is what makes the unit tests'
    synthetic-clock arithmetic deterministic."""
    ordered = sorted(values)
    rank = max(1, math.ceil(0.99 * len(ordered)))
    return ordered[rank - 1]


class SloMonitor:
    """Rolling-window SLO evaluation with alert events and a shed
    signal.

    Observations arrive from the metrics plane (TTFT per admission,
    per-token latency per decode block, ok/error per terminal status);
    :meth:`evaluate` — called once per engine tick — prunes the window,
    compares each declared target, and:

    - entering violation: records one ``slo_violation`` flight-recorder
      event naming every violated target and raises :attr:`should_shed`
      — the engine's admission control stops admitting NEW requests
      while in-flight ones finish (load shedding composes with the
      memory-pressure degraded mode: both squeeze admissions, neither
      touches compiled programs);
    - leaving violation: one ``slo_recovered`` event, shedding clears.

    ``clock`` is injectable (default ``time.monotonic``) so burn /
    recover / shed arithmetic is testable on synthetic time.
    """

    def __init__(self, targets: SloTargets, *, recorder=None,
                 registry=None,
                 clock: Callable[[], float] = time.monotonic):
        if not isinstance(targets, SloTargets):
            raise FriendlyError(
                f"SloMonitor needs SloTargets, got {type(targets).__name__}"
            )
        self.targets = targets
        self._recorder = recorder
        self._clock = clock
        self._ttft: deque[tuple[float, float]] = deque()
        self._per_token: deque[tuple[float, float]] = deque()
        self._finish: deque[tuple[float, bool]] = deque()
        self.should_shed = False
        self.violations_total = 0
        #: CONSECUTIVE burning evaluations (reset on recovery) — the
        #: fleet autoscaler's scale-up signal (serve/fleet.py): a
        #: single bad window hedges noise, a streak means the current
        #: replica count cannot meet the declared targets
        self.burn_ticks = 0
        self._burning = (
            registry.gauge("slo.burning") if registry is not None else None
        )
        self._viol_counter = (
            registry.counter("slo.violations")
            if registry is not None else None
        )
        self._last: dict[str, Any] = {}
        if self._burning is not None:
            self._burning.set(0)

    # -- observations ------------------------------------------------------

    def observe_ttft(self, ms: float, now: float | None = None) -> None:
        self._ttft.append((self._now(now), float(ms)))

    def observe_per_token(self, ms: float,
                          now: float | None = None) -> None:
        self._per_token.append((self._now(now), float(ms)))

    def observe_finish(self, ok: bool, now: float | None = None) -> None:
        self._finish.append((self._now(now), bool(ok)))

    def _now(self, now: float | None) -> float:
        return self._clock() if now is None else now

    def _prune(self, now: float) -> None:
        horizon = now - self.targets.window_s
        for dq in (self._ttft, self._per_token, self._finish):
            while dq and dq[0][0] < horizon:
                dq.popleft()

    # -- evaluation --------------------------------------------------------

    def evaluate(self, now: float | None = None,
                 tick: int | None = None) -> dict:
        """Prune the window, compare every declared target, drive the
        alert/shed state machine; returns the current window state (the
        dict ``ServeMetrics.to_dict()`` embeds under ``"slo"``)."""
        now = self._now(now)
        self._prune(now)
        t = self.targets
        violations: list[dict] = []

        ttft_p99 = (
            _p99([v for _, v in self._ttft]) if self._ttft else None
        )
        if (
            t.ttft_p99_ms is not None and ttft_p99 is not None
            and len(self._ttft) >= t.min_samples
            and ttft_p99 > t.ttft_p99_ms
        ):
            violations.append({
                "slo": "ttft_p99_ms", "value": round(ttft_p99, 3),
                "target": t.ttft_p99_ms,
            })

        ptok_p99 = (
            _p99([v for _, v in self._per_token])
            if self._per_token else None
        )
        if (
            t.per_token_p99_ms is not None and ptok_p99 is not None
            and len(self._per_token) >= t.min_samples
            and ptok_p99 > t.per_token_p99_ms
        ):
            violations.append({
                "slo": "per_token_p99_ms", "value": round(ptok_p99, 4),
                "target": t.per_token_p99_ms,
            })

        err_rate = (
            sum(1 for _, ok in self._finish if not ok) / len(self._finish)
            if self._finish else None
        )
        if (
            t.error_rate is not None and err_rate is not None
            and len(self._finish) >= t.min_samples
            and err_rate > t.error_rate
        ):
            violations.append({
                "slo": "error_rate", "value": round(err_rate, 4),
                "target": t.error_rate,
            })

        burning = bool(violations)
        if burning:
            self.violations_total += 1
            if self._viol_counter is not None:
                self._viol_counter.inc()
        if burning and not self.should_shed:
            if self._recorder is not None:
                self._recorder.record(
                    "slo_violation", tick=tick,
                    violations=violations,
                )
            _log.warning("SLO violation, shedding load: %s", violations)
        elif self.should_shed and not burning:
            if self._recorder is not None:
                self._recorder.record("slo_recovered", tick=tick)
            _log.info("SLO recovered, admissions resume")
        self.should_shed = burning
        self.burn_ticks = self.burn_ticks + 1 if burning else 0
        if self._burning is not None:
            self._burning.set(int(burning))

        self._last = {
            "declared": True,
            "targets": t.to_dict(),
            "window": {
                "ttft_p99_ms": _rnd(ttft_p99, 3),
                "per_token_p99_ms": _rnd(ptok_p99, 4),
                "error_rate": _rnd(err_rate, 4),
                "ttft_samples": len(self._ttft),
                "per_token_samples": len(self._per_token),
                "finish_samples": len(self._finish),
            },
            "burning": burning,
            "burn_ticks": self.burn_ticks,
            "violations": violations,
            "violations_total": self.violations_total,
        }
        return self._last

    def state(self) -> dict:
        """Last evaluation (empty-window shape before the first)."""
        return self._last or {
            "declared": True,
            "targets": self.targets.to_dict(),
            "window": {},
            "burning": False,
            "burn_ticks": 0,
            "violations": [],
            "violations_total": 0,
        }


# --------------------------------------------------------------------------
# Chrome trace-event export (Perfetto-loadable)
# --------------------------------------------------------------------------

#: trace process ids: one pseudo-process for request tracks, one for
#: the engine's tick / dispatch / event tracks
_PID_REQUESTS = 1
_PID_ENGINE = 2
_TID_TICKS = 0
_TID_DISPATCH = 1
_TID_EVENTS = 2

#: terminal span statuses (the exporter closes a request slice on the
#: first of these it sees); ``handed_off`` is terminal on a
#: prefill-role engine — the request continues on a decode replica
_TERMINAL = ("completed", "expired", "failed", "stalled", "handed_off")


def export_chrome_trace(recorder, *, path: str | None = None,
                        extra_meta: dict | None = None) -> dict:
    """FlightRecorder events -> Chrome trace-event JSON.

    Layout (open the file at ui.perfetto.dev, or
    ``chrome://tracing``):

    - process ``serve.requests``: ONE thread/track per request span —
      a complete ("X") slice from span start to its terminal status,
      with every lifecycle event (queued, admitted, prefill, decode,
      ...) as an instant on the same track carrying its attrs;
    - process ``serve.engine``: a ``ticks`` track (one slice per
      scheduler tick), a ``dispatch`` track (one slice per program
      dispatch, named by family — ``decode[T=8]``, ``prefill[16]``),
      and an ``events`` track with everything else (retrace,
      fault_injected, degraded, slo_violation, ...) as instants.

    Timestamps are microseconds since the UNIX epoch via the
    recorder's ``t0_unix`` anchor, so traces recorded by different
    processes (or an engine restored from a snapshot) line up on one
    Perfetto timeline. Output ordering is deterministic: events sort
    by (ts, pid, tid, name), metadata first — two exports of the same
    recorder are byte-identical.

    Returns the trace dict; also writes it to ``path`` when given.
    """
    events = recorder.events()
    t0_unix = getattr(recorder, "t0_unix", 0.0)

    def ts(mono_t: float) -> float:
        return round((t0_unix + mono_t) * 1e6, 3)

    trace: list[dict] = []
    meta: list[dict] = [
        _meta("process_name", _PID_REQUESTS, 0,
              {"name": "serve.requests"}),
        _meta("process_name", _PID_ENGINE, 0, {"name": "serve.engine"}),
        _meta("thread_name", _PID_ENGINE, _TID_TICKS, {"name": "ticks"}),
        _meta("thread_name", _PID_ENGINE, _TID_DISPATCH,
              {"name": "dispatch"}),
        _meta("thread_name", _PID_ENGINE, _TID_EVENTS, {"name": "events"}),
    ]

    # request spans -> one track per span
    spans: dict[int, list[dict]] = {}
    for ev in events:
        if ev.get("span_name") == "request" and "span" in ev:
            spans.setdefault(ev["span"], []).append(ev)
    for sid in sorted(spans):
        evs = spans[sid]
        start = next((e for e in evs if e["name"] == "start"), None)
        req_id = (
            start.get("attrs", {}).get("id", sid)
            if start is not None else sid
        )
        tid = int(req_id)
        meta.append(_meta("thread_name", _PID_REQUESTS, tid,
                          {"name": f"request {req_id}"}))
        end = next(
            (e for e in evs if e["name"] in _TERMINAL), None
        )
        if start is not None:
            dur = (
                round((end["t"] - start["t"]) * 1e6, 3)
                if end is not None else 0.0
            )
            trace.append({
                "name": (
                    f"request {req_id}"
                    + (f" [{end['name']}]" if end is not None else "")
                ),
                "ph": "X", "pid": _PID_REQUESTS, "tid": tid,
                "ts": ts(start["t"]), "dur": dur,
                "args": dict(start.get("attrs", {})),
            })
        for ev in evs:
            if ev is start:
                continue
            trace.append({
                "name": ev["name"], "ph": "i", "s": "t",
                "pid": _PID_REQUESTS, "tid": tid, "ts": ts(ev["t"]),
                "args": _instant_args(ev),
            })

    # engine tracks
    for ev in events:
        if ev.get("span_name") == "request":
            continue
        name = ev["name"]
        if name == "tick":
            dur_ms = ev.get("attrs", {}).get("ms", 0.0)
            trace.append({
                "name": f"tick {ev.get('tick', '?')}",
                "ph": "X", "pid": _PID_ENGINE, "tid": _TID_TICKS,
                "ts": ts(ev["t"] - dur_ms * 1e-3),
                "dur": round(dur_ms * 1e3, 3),
                "args": _instant_args(ev),
            })
        elif name == "dispatch":
            attrs = ev.get("attrs", {})
            dur_ms = attrs.get("ms", 0.0)
            trace.append({
                "name": attrs.get("family", "dispatch"),
                "ph": "X", "pid": _PID_ENGINE, "tid": _TID_DISPATCH,
                "ts": ts(ev["t"] - dur_ms * 1e-3),
                "dur": round(dur_ms * 1e3, 3),
                "args": _instant_args(ev),
            })
        else:
            trace.append({
                "name": name, "ph": "i", "s": "t",
                "pid": _PID_ENGINE, "tid": _TID_EVENTS,
                "ts": ts(ev["t"]), "args": _instant_args(ev),
            })

    trace.sort(key=lambda e: (e["ts"], e["pid"], e["tid"], e["name"]))
    doc = {
        "traceEvents": meta + trace,
        "displayTimeUnit": "ms",
        "otherData": {
            "generator": "mmlspark_tpu.core.perf.export_chrome_trace",
            "t0_unix": round(t0_unix, 6),
            **(extra_meta or {}),
        },
    }
    if path is not None:
        from mmlspark_tpu.core.telemetry import atomic_write_text

        atomic_write_text(path, json.dumps(
            doc, sort_keys=True, separators=(",", ":"), default=str,
        ))
        _log.info("chrome trace: %d events -> %s",
                  len(doc["traceEvents"]), path)
    return doc


def _meta(name: str, pid: int, tid: int, args: dict) -> dict:
    return {"name": name, "ph": "M", "pid": pid, "tid": tid,
            "args": args, "ts": 0.0}


def _instant_args(ev: dict) -> dict:
    args = dict(ev.get("attrs", {}))
    if "tick" in ev:
        args["tick"] = ev["tick"]
    return args
