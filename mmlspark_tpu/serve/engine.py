"""``ServeEngine`` — the public continuous-batching serving API.

Turns the repo's static-shape KV-cache decode (``models/generate.py``)
into a multi-tenant engine: requests of different prompt lengths and
arrival times share ONE jitted decode program over the slot pool's
fixed-shape buffers. The decode program is a FUSED BLOCK
(``models.generate.make_decode_block``): ``lax.scan`` over up to
``decode_block`` greedy micro-steps inside one dispatch, sampling and
advancing per-slot positions on device, with an on-device live/EOS/
budget mask so finished slots emit pads without branching — ONE host
sync per block instead of one per token, which is what the per-token
latency of a dispatch-bound small-model tick is made of. Block sizes
are clamped to a power-of-two ladder, so at most
``num_decode_blocks`` = O(log decode_block) decode programs ever
compile (asserted by ``tests/test_serve.py`` via
``decode_compile_count``; the ladder shrinks near per-request budgets
to keep token-for-token parity with ``generate()``). Prefill is its own
jitted program, BUCKETED by prompt length: prompts right-pad to
power-of-two buckets, so at most O(log cache_len) prefill programs ever
compile (``prefill_compile_count`` <= ``num_prefill_buckets``) —
joiners pay a bucketed prefill, the steady-state decode tick never
recompiles. The block reads each slot's cache through the length-aware
split-KV kernel (``ops/flash_attention.flash_decode``, with dead rows'
live lengths zeroed mid-block) and DONATES the pool's buffer pytree
plus the device positions/live mask, so all decode state updates in
place on device (docs/SERVING.md has the donation contract).

Usage::

    engine = ServeEngine(graph, variables, slots=8)
    rid = engine.submit(prompt_ids, max_new_tokens=32)   # queued
    results = engine.run()                                # drain
    results[rid].tokens                                   # prompt + gen

``submit`` is admission-controlled (bounded queue raises the typed
:class:`FriendlyError` when full) and validates per-request budgets
against the pool's ``cache_len``; ``step()`` runs one scheduler tick
(admit -> fused decode -> retire) and returns the requests that finished
on it; ``run()`` loops ``step()`` until idle. Decode is greedy
(temperature-0) — identical tokens to ``generate()`` per request, which
is the engine's correctness contract.

A model that GENERATES BY DIFFUSION OVER BLOCKS (a graph whose ``extra``
has a ``block`` length, ``hybrid_lm(block=...)``) is served by the same
loop with two other programs, chosen at construction: admission prefills
the prompt's whole blocks under a block-causal mask and emits no token,
and the tick runs ONE denoising program over whole blocks
(``models.generate.make_denoise_block``) that commits several tokens a
slot a step and delivers whole blocks (docs/SERVING.md "Block
generation").
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from mmlspark_tpu.core import integrity
from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.integrity import SnapshotCorruption
from mmlspark_tpu.core.faults import (
    EngineKilled,
    FaultInjector,
    is_resource_exhausted,
    is_transient,
)
from mmlspark_tpu.core.perf import (
    SloMonitor,
    SloTargets,
    analyze_jit_cost,
    parse_slo_spec,
)
from mmlspark_tpu.core.telemetry import (
    FlightRecorder,
    RetraceWatchdog,
    SpanTracer,
)
from mmlspark_tpu.models.generate import (
    _cached_apply,
    counts_routing,
    declares_cache_kinds,
    greedy_next,
    init_cache,
    make_decode_block,
    make_denoise_block,
    routing_totals,
    sparse_prefill_rows,
)
from mmlspark_tpu.parallel.mesh import make_mesh, parse_mesh_axes
from mmlspark_tpu.parallel.sharding import (
    TRANSFORMER_TP_RULES,
    shard_params,
)
from mmlspark_tpu.serve.cache_pool import SlotCachePool
from mmlspark_tpu.serve.metrics import ServeMetrics
from mmlspark_tpu.serve.scheduler import (
    ContinuousBatchScheduler,
    RequestResult,
    ServeRequest,
)
from mmlspark_tpu.testing.compile_guard import (
    ProgramCountingJit,
    jit_cache_size,
)


def _routing_drops(graph) -> bool:
    """Whether ``graph`` routes tokens to experts in a way that can DROP
    one (capacity dispatch): such routing is not causal over a padded
    window, so the model prefills at exact length and takes no chunks.
    A builder says so in ``extra["routing_drops"]``; one that records
    only ``n_experts`` (``transformer_lm_moe``) drops."""
    return bool(graph.extra.get("routing_drops",
                                graph.extra.get("n_experts")))


def _routing_attrs(stats, steps: int, passes: int | None = None) -> dict:
    """The routing counters a decode block or a prefill fetched beside
    its tokens, as event attributes: per routed layer the held experts
    hit per micro-step, and per PASS (``passes``, the micro-steps where
    not given) the pairs that fell on held experts and the rows the
    expert products multiplied, pad rows of their tiles too. A pass is
    one token a slot, or one block's rows a slot: a denoising program's
    step that closes a block beside the next block's step is two."""
    if not stats:
        return {}
    per = {"experts_hit": steps}
    return {name: round(float(np.mean(total))
                        / max(per.get(name, passes or steps), 1), 3)
            for name, total in stats.items()}


def _resolve_mesh(mesh):
    """Engine ``mesh`` argument -> jax Mesh or None. Accepts a built
    Mesh, an axes mapping (``{"data": -1, "model": 2}``), or the CLI
    string spelling (``"data=4,model=2"``)."""
    if mesh is None:
        return None
    if isinstance(mesh, str):
        mesh = parse_mesh_axes(mesh)
    if isinstance(mesh, dict):
        return make_mesh(mesh)
    return mesh


class ServeEngine:
    def __init__(self, graph, variables, *, slots: int = 4,
                 cache_len: int | None = None, max_queue: int = 16,
                 pad_id: int = 0, decode_block: int = 32,
                 mesh=None,
                 recorder: FlightRecorder | None = None,
                 faults: FaultInjector | None = None,
                 retry_limit: int = 3,
                 retry_backoff_s: float = 0.02,
                 degrade_recover_ticks: int = 8,
                 slo=None,
                 paged: bool = False, page_size: int | None = None,
                 num_pages: int | None = None,
                 prefix_cache: bool = False,
                 replica: int | None = None,
                 snapshot_every_ticks: int | None = None,
                 kv_dtype: str = "bf16",
                 quantize_weights: bool = False,
                 role: str = "both",
                 prefill_chunk: int | None = None,
                 max_fills: int | None = None,
                 async_host: bool = False,
                 registry=None):
        if not graph.extra.get("causal", False):
            raise FriendlyError(
                f"serving needs a causal LM; '{graph.name}' has "
                "causal=False"
            )
        #: a model that generates by diffusion over blocks of this many
        #: positions (``hybrid_lm``'s ``block``); 0: a token at a time
        self._block_len = int(graph.extra.get("block") or 0)
        if self._block_len:
            refused = [what for what, on in (
                ("async_host", async_host), ("paged", paged),
                (f"kv_dtype={kv_dtype!r}", kv_dtype != "bf16"),
                ("a mesh", mesh is not None),
                ("prefill_chunk", prefill_chunk is not None),
                (f"role={role!r} (the fleet's KV hand-off)",
                 role != "both")) if on]
            if refused:
                raise FriendlyError(
                    f"'{graph.name}' generates by diffusion over blocks of "
                    f"{self._block_len}; its denoising program runs on one "
                    "device over the dense bf16 pool, with a synchronous "
                    f"host loop and whole prefills: drop {', '.join(refused)}"
                )
        max_len = graph.input_shape[0] if graph.input_shape else None
        if cache_len is None:
            if not max_len:
                raise FriendlyError(
                    f"'{graph.name}' records no input_shape; pass "
                    "cache_len explicitly to size the slot KV buffers"
                )
            cache_len = max_len
        if (
            max_len
            and cache_len > max_len
            and graph.extra.get("pos_embedding", "learned") == "learned"
        ):
            raise FriendlyError(
                f"cache_len ({cache_len}) exceeds the learned position "
                f"table ({max_len}); build the model with a larger "
                "max_len or pos_embedding='rope'"
            )
        window = graph.extra.get("window")
        if window and window < cache_len:
            raise FriendlyError(
                f"'{graph.name}' uses a sliding window ({window}) "
                f"smaller than cache_len ({cache_len}); the slot pool "
                "keeps a ring only for a block that declares one "
                "(cache_spec(), as hybrid_lm's window layers do), and "
                f"'{graph.name}' has one window for linear rows. Serve "
                "with cache_len <= window, or build the model without "
                "window"
            )
        if decode_block < 1:
            raise FriendlyError(
                f"decode_block must be >= 1, got {decode_block} "
                "(1 = per-token dispatch, larger fuses T micro-steps "
                "into one device program)"
            )
        # chunked prefill (docs/SERVING.md "Chunked prefill"): cap the
        # widest prefill dispatch at ``prefill_chunk`` tokens — a long
        # prompt's fill becomes a sequence of bounded chunk dispatches
        # interleaved with decode ticks, so one joiner can never
        # head-of-line-block every co-resident stream. Chunk widths
        # live on the SAME power-of-two ladder as prefill buckets
        # ({8, 16, ..., prefill_chunk}), so the compile pin tightens to
        # ``prefill_compile_count <= num_chunk_buckets``.
        if prefill_chunk is not None:
            if (
                prefill_chunk < 8
                or prefill_chunk & (prefill_chunk - 1)
            ):
                raise FriendlyError(
                    f"prefill_chunk must be a power of two >= 8 (the "
                    f"prefill bucket ladder's floor), got {prefill_chunk}"
                )
            if prefill_chunk > cache_len:
                raise FriendlyError(
                    f"prefill_chunk ({prefill_chunk}) exceeds cache_len "
                    f"({cache_len}); a chunk wider than the KV buffers "
                    "can never be dispatched — drop the flag or shrink "
                    "the chunk"
                )
            if _routing_drops(graph):
                raise FriendlyError(
                    f"'{graph.name}' is a MoE model, which prefills at "
                    "exact length (expert-capacity routing is not "
                    "causal, so padded chunk windows could change real "
                    "tokens' expert assignment); chunked prefill "
                    "requires bucketed prefill — drop prefill_chunk"
                )
        self._prefill_chunk = prefill_chunk
        # at most this many chunked fills at once (the queue waits; None:
        # no cap). Every fill in flight advances one chunk a tick, so the
        # cap is the tick's prefill budget beside its decode block, as
        # prefill_chunk is a fill's; each fill also holds a carry of the
        # whole cache_len for every layer until it lands
        if max_fills is not None and (prefill_chunk is None
                                      or int(max_fills) < 1):
            raise FriendlyError(
                f"max_fills ({max_fills}) caps the chunked fills in flight: "
                "it needs prefill_chunk and at least 1")
        self._max_fills = max_fills
        # pipelined async host loop (docs/SERVING.md "Async host
        # loop"): dispatch block N+1 behind block N's in-flight
        # execution and only then fetch N's tokens, so host work
        # (scheduling, SLO eval, telemetry, fault hooks) overlaps into
        # device time. Token streams stay bit-identical — pipelining
        # reorders HOST work, never device programs' inputs (see
        # _decode_phase_async for the identity-fence and deferred-free
        # machinery that guarantees it).
        self._async_host = bool(async_host)
        #: in-flight decode block record (async mode): set at dispatch,
        #: consumed by the NEXT tick's fetch
        self._inflight: dict | None = None
        #: monotone dispatch generation stamping the pools' deferred
        #: frees — a freed slot returns to the free list only after the
        #: block that saw it live has been fetched
        self._dispatch_gen = 0
        #: when the previously fetched block's outputs materialized —
        #: the queued-vs-executing attribution anchor for the next
        #: pipelined dispatch interval (core/perf.py record_dispatch)
        self._prev_block_done = 0.0
        self.graph = graph
        self.pad_id = pad_id
        self.cache_len = cache_len
        # floor to a power of two: block sizes live on the ladder
        # {1, 2, 4, ..., decode_block}, so the scan-length static arg
        # compiles O(log) program variants, never one per budget
        self.decode_block = 1 << (int(decode_block).bit_length() - 1)
        # sharded serving (docs/SERVING.md "Sharded serving"): with a
        # mesh, params commit to the model axis by the Megatron rules
        # and the pool's slot-batched state to the data axis; GSPMD
        # partitions the SAME prefill/decode programs — XLA inserts the
        # collectives, token streams stay bit-identical to the
        # single-device engine, and the compile-count pins hold because
        # every per-tick input is committed to a fixed NamedSharding
        self.mesh = _resolve_mesh(mesh)
        if self.mesh is not None:
            # attention runs its kernels per shard of THIS mesh
            graph = self.graph = graph.with_mesh(self.mesh)
        # weight-only int8 serving (docs/PERFORMANCE.md "Quantized
        # decode"): the device-resident weights are per-channel int8
        # (min_size=0 — at decode batch sizes EVERY matmul is
        # bandwidth-bound) and each jitted program dequantizes to bf16
        # INSIDE jit, so XLA fuses the convert into the consuming
        # matmul and HBM streams half the bytes per forward. Under a
        # mesh the quantized pytree is REPLICATED: its {int8, scale}
        # dict leaves are outside the Megatron path rules, so the
        # weight-HBM win trades away tensor-parallel weight sharding
        # (docs/SERVING.md records the trade).
        self._quantized_weights = bool(quantize_weights)
        if quantize_weights:
            from mmlspark_tpu.ops.quantize import (
                quantize_weights as _quantize_variables,
            )

            qvars = _quantize_variables(variables, min_size=0)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                qvars = jax.device_put(
                    qvars, NamedSharding(self.mesh, PartitionSpec())
                )
            self.variables = qvars
        else:
            self.variables = (
                shard_params(variables, self.mesh, TRANSFORMER_TP_RULES)
                if self.mesh is not None else variables
            )
        # every jitted program below dequantizes through this hook; the
        # identity on unquantized engines keeps traces byte-identical
        # to previous builds
        if quantize_weights:
            from mmlspark_tpu.ops.quantize import dequantize_weights
            _deq = dequantize_weights
        else:
            def _deq(v):
                return v
        # paged KV cache (docs/SERVING.md "Paged KV cache"): the
        # PagedCachePool virtualizes slot memory behind fixed-shape page
        # stores + per-slot page tables — same compiled programs, same
        # donation/sharding/compile-pin contracts, but HBM scales with
        # pages actually mapped and shared prompt prefixes prefill once
        if not paged and (
            page_size is not None or num_pages is not None or prefix_cache
        ):
            raise FriendlyError(
                "page_size/num_pages/prefix_cache configure the paged "
                "KV cache; pass paged=True to enable it"
            )
        self._paged = bool(paged)
        self._prefix_cache = bool(paged and prefix_cache)
        self.kv_dtype = kv_dtype
        if paged:
            from mmlspark_tpu.serve.paging import PagedCachePool

            self.pool = PagedCachePool(
                graph, variables, slots, cache_len, mesh=self.mesh,
                page_size=page_size, num_pages=num_pages,
                prefix_cache=prefix_cache, kv_dtype=kv_dtype,
            )
        else:
            self.pool = SlotCachePool(graph, variables, slots, cache_len,
                                      mesh=self.mesh, kv_dtype=kv_dtype)
        # replica identity (serve/supervisor.py): tags every fault-hook
        # firing (so replica-pinned kills target THIS engine) and
        # namespaces the registry metric names per replica
        if replica is not None and replica < 0:
            raise FriendlyError(
                f"replica index must be >= 0, got {replica}"
            )
        self._replica = replica
        # disaggregated-fleet role (docs/SERVING.md "Disaggregated
        # fleet"): "prefill" engines run admission + prefill only and
        # retire each request as "handed_off" with its KV payload in
        # the outbox; "decode" engines adopt those payloads by direct
        # KV write (and keep FULL prefill capability — the fallback
        # when a hand-off is lost keeps streams bit-identical);
        # "both" (the default) is the classic homogeneous engine.
        if role not in ("both", "prefill", "decode"):
            raise FriendlyError(
                f"role must be 'both', 'prefill' or 'decode', got "
                f"{role!r}"
            )
        if role != "both" and declares_cache_kinds(graph):
            raise FriendlyError(
                f"'{graph.name}' declares its cache geometry (kinds "
                f"{', '.join(map(repr, declares_cache_kinds(graph)))}: "
                "rings, latent rows, a convolution's state, keys and values "
                "of different widths); the fleet's KV hand-off ships linear "
                "rows of one width — serve it with role='both'"
            )
        self.role = role
        #: KV hand-off payloads awaiting collection by the fleet
        #: (prefill-role engines fill this; ``take_handoffs`` drains)
        self._outbox: list[dict] = []
        #: engine-local request id -> pending hand-off payload, popped
        #: by the admit loop for the direct-KV-write adoption path
        self._handoffs: dict[int, dict] = {}
        # periodic snapshot cadence: every N ticks, step() refreshes
        # ``last_snapshot`` through the serve.snapshot fault hook — the
        # supervisor's recovery point. None (the default) keeps
        # snapshotting fully caller-driven, zero work per tick.
        if snapshot_every_ticks is not None and snapshot_every_ticks < 1:
            raise FriendlyError(
                f"snapshot_every_ticks must be >= 1, got "
                f"{snapshot_every_ticks}"
            )
        self._snapshot_every = snapshot_every_ticks
        self._last_snapshot: dict | None = None
        #: set when an EngineKilled escaped and the device resources
        #: were parked — the engine refuses further steps (restore
        #: from a snapshot instead)
        self._dead = False
        # ``registry``: hand the metrics plane a shared (usually
        # namespaced — core/telemetry.NamespacedRegistry) registry so
        # several engines' expositions merge collision-free; None (the
        # default) keeps the engine's registry private as before
        self.metrics = ServeMetrics(
            graph.name, slots, registry=registry,
            decode_block=self.decode_block,
            mesh_shape=(
                {k: int(v) for k, v in self.mesh.shape.items()}
                if self.mesh is not None else {}
            ),
            mesh_devices=(
                int(self.mesh.size) if self.mesh is not None else 1
            ),
            cache_pool_bytes_per_device=(
                self.pool.device_bytes_per_device()
            ),
            kv_dtype=kv_dtype,
            prefill_chunk=prefill_chunk or 0,
            async_host=self._async_host,
            namespace=(
                f"replica{replica}." if replica is not None else ""
            ),
        )
        if paged:
            self.metrics.attach_paging(self.pool.paging_stats)
        #: flight recorder (core/telemetry): one span per request
        #: lifecycle — queued -> admitted -> prefill[bucket] -> decode
        #: ticks -> finished/expired — dumpable as events.jsonl via the
        #: CLI's ``--telemetry-dir`` (docs/OBSERVABILITY.md)
        self.recorder = recorder if recorder is not None else FlightRecorder()
        self._tracer = SpanTracer(self.recorder)
        self._spans: dict[int, object] = {}
        self._sched = ContinuousBatchScheduler(self.pool,
                                               max_queue=max_queue)
        self._next_id = 0

        # resilience layer (docs/SERVING.md "Failure semantics"):
        # transient dispatch errors retry behind capped deterministic
        # backoff; RESOURCE_EXHAUSTED steps down the decode-block
        # ladder and caps admissions (graceful degradation — NO new XLA
        # programs, the ladder sizes already exist); a request that
        # still cannot make progress is QUARANTINED (terminal status
        # "failed", slot freed, device live mask forced dead) instead
        # of killing run(). ``faults`` is the deterministic injection
        # harness (core/faults.py); None (the default) keeps every hook
        # a single attribute check — zero work on the hot path.
        if retry_limit < 0:
            raise FriendlyError(
                f"retry_limit must be >= 0, got {retry_limit}"
            )
        self._faults = faults
        self._retry_limit = retry_limit
        self._retry_backoff_s = retry_backoff_s
        self._degrade_recover_ticks = max(1, degrade_recover_ticks)
        #: memory-pressure degradation state: the current decode-block
        #: ceiling (walks DOWN the existing power-of-two ladder on OOM,
        #: re-escalates after ``degrade_recover_ticks`` clean ticks)
        #: and the concurrent-admission cap
        self._block_cap = self.decode_block
        self._admit_cap = slots
        self._ok_ticks = 0
        #: vocab for token-stream validation (poison detection); None
        #: when the builder records no vocab — validation then only
        #: rejects negatives
        self._vocab = graph.extra.get("vocab_size")
        # SLO plane (docs/OBSERVABILITY.md "Declaring SLOs"): ``slo``
        # accepts the CLI string spelling, SloTargets, or a prebuilt
        # SloMonitor. When targets burn, the monitor's shed signal
        # suppresses NEW admissions (in-flight requests finish) — load
        # shedding composes with memory-pressure degradation: both
        # squeeze the admit loop, neither touches compiled programs.
        if isinstance(slo, str):
            slo = parse_slo_spec(slo)
        if isinstance(slo, SloTargets):
            slo = SloMonitor(slo, recorder=self.recorder,
                             registry=self.metrics.registry)
        self._slo: SloMonitor | None = slo
        if slo is not None:
            self.metrics.attach_slo(slo)
        if self._faults is not None and self._faults.listener is None:
            # injected faults land in the same metrics + event timeline
            # as their consequences (retries, quarantines, degradation)
            def _on_fault(kind: str, site: str) -> None:
                self.metrics.record_fault(kind)
                self.recorder.record(
                    "fault_injected", tick=self.tick, kind=kind,
                    site=site,
                )
            self._faults.listener = _on_fault

        # bucketed prefill: prompts are right-padded to power-of-two
        # length buckets, so the prefill program count is O(log
        # cache_len) instead of O(distinct prompt lengths). Causality
        # makes the pads invisible: pad positions sit AFTER every real
        # token, the real positions' K/V and logits cannot see them, and
        # ``last`` (traced, so no retrace per value) slices the true
        # last-token logits out of the padded row. A model whose routing
        # can DROP a token opts out — expert-capacity routing is not
        # causal (a pad consumes capacity that can change a REAL token's
        # expert), so it keeps exact-length prefill. A family whose
        # routing is per token and dropless (``routing_drops`` False in
        # the builder's extra) buckets like any other.
        self._bucketed = not _routing_drops(graph)
        #: whether the decode block and the prefill hand back routing
        #: counters beside their tokens (models/generate.py)
        self._routed = counts_routing(graph)
        routed = self._routed

        def _prefill(variables, prompt, last):
            # (1, B) padded prompt -> first greedy token (from position
            # ``last``, the true prompt end) + a length-B linear cache;
            # jit retraces per distinct BUCKET
            cache = init_cache(graph, variables, 1, prompt.shape[1])
            variables = _deq(variables)
            counters = {} if routed else None
            logits, cache = _cached_apply(
                graph, variables, prompt, cache, 0,
                # the bucket's pads route nowhere
                valid=(jnp.arange(prompt.shape[1]) <= last)[None, :]
                if routed else None,
                counters=counters,
            )
            cur = jax.lax.dynamic_slice_in_dim(
                logits, last, 1, axis=1
            )[:, 0]
            if routed:
                return greedy_next(cur), cache, routing_totals(counters)
            return greedy_next(cur), cache

        # both programs run behind the retrace watchdog: any compile
        # beyond the design's budget (decode: one per ladder block
        # size, prefill: one per bucket) is logged the moment it
        # happens with the abstract shapes that triggered it, and lands
        # in the flight recorder's event timeline next to the request
        # that caused it
        # ProgramCountingJit makes the counts true XLA-program counts
        # even under a mesh, where jax's raw signature cache would
        # re-register NamedSharding-committed args as "new shapes"
        # (testing/compile_guard.py) — the pins and watchdog budgets
        # therefore hold unchanged on sharded engines
        self._prefill = RetraceWatchdog(
            ProgramCountingJit(jax.jit(_prefill)), "serve.prefill",
            registry=self.metrics.registry, recorder=self.recorder,
            expected_programs=self.num_prefill_buckets,
        )

        # prefix-cache RESUME prefill (docs/SERVING.md "Paged KV
        # cache"): a prompt sharing a cached prefix runs the forward
        # over the REMAINDER only, against the prefix's gathered linear
        # K/V. ``pos``/``last`` are traced, so programs are keyed by the
        # remainder BUCKET alone — the same O(log cache_len) ceiling as
        # full prefill.
        def _resume(variables, ids, cache, pos, last):
            logits, cache = _cached_apply(graph, _deq(variables), ids,
                                          cache, pos)
            cur = jax.lax.dynamic_slice_in_dim(
                logits, last, 1, axis=1
            )[:, 0]
            return greedy_next(cur), cache

        self._resume = None
        if self._prefix_cache:
            self._resume = RetraceWatchdog(
                ProgramCountingJit(jax.jit(_resume)), "serve.resume",
                registry=self.metrics.registry, recorder=self.recorder,
                expected_programs=self.num_prefill_buckets,
            )

        # the chunked-fill program IS the resume body: one forward over
        # a chunk window of the sequence against the fill's carry cache
        # (a full-cache_len linear cache), keyed by the chunk BUCKET
        # alone — ``pos``/``last`` are traced and the carry's shape is
        # fixed, so at most ``num_chunk_buckets`` programs ever compile
        # unlike resume (one shot, output handed straight to
        # write_prefill), the chunk program's output cache RE-ENTERS the
        # next chunk call as the carry — under a mesh the outputs are
        # pinned replicated so the signature reaches its fixed point on
        # the first call instead of retracing on GSPMD's own choice
        chunk_kwargs = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            chunk_kwargs["out_shardings"] = NamedSharding(
                self.mesh, PartitionSpec()
            )
        self._chunk = None
        if self._prefill_chunk is not None:
            self._chunk = RetraceWatchdog(
                ProgramCountingJit(jax.jit(_resume, **chunk_kwargs)),
                "serve.chunk",
                registry=self.metrics.registry, recorder=self.recorder,
                expected_programs=self.num_chunk_buckets,
            )
        # the FUSED decode block (models.generate.make_decode_block):
        # lax.scan over t greedy micro-steps with the scan length
        # static (one program per ladder size) and the whole device
        # decode state DONATED — the slot-pool cache pytree AND the
        # per-slot positions/live mask update in place on device.
        # Contract: the engine immediately rebinds pool.buffers/
        # positions/live to the block's outputs and nothing else may
        # hold the donated references (docs/SERVING.md).
        # under a mesh the block's loop-carried outputs are PINNED to
        # the pool's canonical shardings (out_shardings): tick N's
        # outputs re-enter tick N+1 with byte-identical placement, so
        # the signature reaches its fixed point on the first call and
        # the ladder pins hold — GSPMD would otherwise pick output
        # shardings of its own and every tick would re-register
        jit_kwargs = {}
        if self.mesh is not None:
            slot_sh = self.pool.slot_sharding
            jit_kwargs["out_shardings"] = (
                slot_sh, slot_sh, self.pool.kv_shardings, slot_sh,
            )
        _raw_block = make_decode_block(graph, pad_id)
        if self._quantized_weights:
            # dequantize INSIDE the jitted block (same signature, same
            # static/donate argnums — the jit contract is untouched);
            # the int8 weights convert once per dispatch and XLA fuses
            # the convert into each consuming matmul
            def _block(variables, buffers, pos, live, tok, rem, eos, t):
                return _raw_block(_deq(variables), buffers, pos, live,
                                  tok, rem, eos, t)
        else:
            _block = _raw_block
        self._decode = RetraceWatchdog(
            ProgramCountingJit(jax.jit(
                _block,
                static_argnums=(7,), donate_argnums=(1, 2, 3),
                **jit_kwargs,
            )),
            "serve.decode",
            registry=self.metrics.registry, recorder=self.recorder,
            expected_programs=self.num_decode_blocks,
        )
        if self._block_len:
            self._block_programs(_deq)

    def _block_programs(self, deq) -> None:
        """The two programs of a model that generates by diffusion over
        blocks, in the places of the prefill and the decode block: a
        prefill of a prompt's whole blocks that stops before the head
        (no token comes of it), and the denoising program, ONE for every
        count of blocks (the count is traced; at most ``decode_block``
        tokens' worth, ``_most_blocks``)."""
        graph, routed = self.graph, self._routed
        self._denoise_steps = int(graph.extra["denoise_steps"])
        self._most_blocks = max(1, self.decode_block // self._block_len)

        def _prefill(variables, prompt, length):
            # (1, B) padded prompt whose first ``length`` positions are
            # whole blocks -> a length-B linear cache; pads route nowhere
            cache = init_cache(graph, variables, 1, prompt.shape[1])
            _, cache = _cached_apply(
                graph, deq(variables), prompt, cache, 0,
                valid=(jnp.arange(prompt.shape[1]) < length)[None, :]
                if routed else None,
                counters={} if routed else None, head=False)
            return cache

        self._prefill = RetraceWatchdog(
            ProgramCountingJit(jax.jit(_prefill)), "serve.prefill",
            registry=self.metrics.registry, recorder=self.recorder,
            expected_programs=self.num_prefill_buckets,
        )
        denoise, most = make_denoise_block(graph), self._most_blocks

        # named as the token engine's program: the trace's decode metrics
        # find a decode program by this name
        def decode_block(variables, buffers, pos, live, tok, masked, rem,
                         n_blocks):
            return denoise(deq(variables), buffers, pos, live, tok, masked,
                           rem, n_blocks, most)

        self._decode = RetraceWatchdog(
            ProgramCountingJit(jax.jit(decode_block,
                                       donate_argnums=(1, 2, 3))),
            "serve.decode",
            registry=self.metrics.registry, recorder=self.recorder,
            expected_programs=1,
        )

    # -- prefill buckets ---------------------------------------------------

    def prefill_bucket(self, prompt_len: int) -> int:
        """Padded length the prefill program runs at for a prompt of
        ``prompt_len``: the next power of two >= max(prompt_len, 8),
        capped at ``cache_len`` (admission control guarantees
        prompt_len < cache_len, so the cap always covers the prompt).
        MoE engines bucket at exact length (see ``__init__``)."""
        if not self._bucketed:
            return prompt_len
        bucket = 8
        while bucket < prompt_len:
            bucket *= 2
        return min(bucket, self.cache_len)

    def chunk_bucket(self, n: int) -> int:
        """Padded width the chunked-fill program runs at for a chunk of
        ``n`` real tokens: the next power of two >= max(n, 8), capped at
        ``prefill_chunk``. Intermediate chunks are exactly
        ``prefill_chunk`` wide (the top bucket); only a fill's FINAL
        chunk can land on a smaller rung."""
        bucket = 8
        while bucket < n:
            bucket *= 2
        return min(bucket, self._prefill_chunk)

    @property
    def num_chunk_buckets(self) -> int:
        """How many distinct chunked-fill programs CAN exist — one per
        ladder width in {8, 16, ..., prefill_chunk}; 0 with chunking
        off."""
        if self._prefill_chunk is None:
            return 0
        return self._prefill_chunk.bit_length() - 3

    @property
    def num_prefill_buckets(self) -> int:
        """How many distinct prefill programs CAN exist for this engine
        — the ceiling the compile-guard tests pin prefill to. With
        chunked prefill the monolithic program never runs and the
        ceiling is the CHUNK ladder's (``num_chunk_buckets`` <= the
        monolithic count, since the chunk cap truncates the bucket
        ladder)."""
        if self._prefill_chunk is not None:
            return self.num_chunk_buckets
        return len({
            self.prefill_bucket(p) for p in range(1, self.cache_len)
        })

    # -- decode-block ladder ----------------------------------------------

    def _block_size(self, min_rem: int) -> int:
        """This tick's fused-block scan length: the largest ladder power
        of two <= min(decode_block, minimum remaining budget over active
        slots). Clamping to the min budget is the "shrink near budgets"
        parity rule: no slot can overrun its budget mid-block, so budget
        exhaustion only ever lands exactly on a block boundary (the only
        mid-block death is EOS, which the on-device mask handles).
        Under memory-pressure degradation the ceiling is ``_block_cap``
        (<= decode_block) — still on the ladder, so no new programs."""
        cap = min(self._block_cap, max(1, min_rem))
        t = 1
        while t * 2 <= cap:
            t *= 2
        return t

    @property
    def num_decode_blocks(self) -> int:
        """How many distinct fused decode-block programs CAN exist for
        this engine — one per ladder size T in {1, 2, 4, ...,
        decode_block}, the ceiling the compile-guard tests pin decode
        to. Scan iterations inside a block share one program; only
        distinct static scan lengths compile separately. A model that
        generates by diffusion over blocks has ONE denoising program,
        whose count of blocks is traced."""
        if self._block_len:
            return 1
        return self.decode_block.bit_length()

    # -- fault handling ----------------------------------------------------

    @property
    def degraded(self) -> bool:
        """True while memory-pressure degradation holds the engine
        below full service (reduced block ladder ceiling or admission
        cap); the recovery probe clears it."""
        return (
            self._block_cap < self.decode_block
            or self._admit_cap < self.pool.num_slots
        )

    def _backoff(self, attempts: int) -> None:
        """Capped DETERMINISTIC backoff before a retry: linear in the
        attempt number, no jitter — reproducibility is worth more to
        this in-process engine than thundering-herd protection."""
        self.metrics.record_retry()
        self.recorder.record("retry", tick=self.tick, attempt=attempts)
        if self._retry_backoff_s > 0:
            time.sleep(self._retry_backoff_s * attempts)

    def _note_oom(self, tick: int, site: str) -> None:
        """Graceful degradation on RESOURCE_EXHAUSTED: step DOWN the
        existing power-of-two decode-block ladder (never a new XLA
        program) and tighten the admission cap; at the ladder floor,
        preempt the youngest active request — its emitted tokens fold
        into a resume prefix and it re-queues, so memory pressure costs
        latency, not data. A recovery probe re-escalates after
        ``degrade_recover_ticks`` clean ticks."""
        if self._block_cap > 1:
            self._block_cap //= 2
        elif len(self._sched.active) > 1:
            # youngest active slot: the most recently admitted request
            # has the least sunk decode work to re-prefill on resume
            slot = next(reversed(self._sched.active))
            req = self._sched.preempt(slot)
            self._sched.requeue(req)
            self.metrics.record_preemption()
            span = self._spans.get(req.id)
            if span is not None:
                span.event("preempted", tick=tick, slot=slot,
                           prefix_len=len(req.prefix))
            self.recorder.record(
                "preempted", tick=tick, id=req.id, slot=slot,
                prefix_len=len(req.prefix),
            )
        self._admit_cap = max(1, self._admit_cap - 1)
        self._ok_ticks = 0
        self.metrics.set_degraded(True)
        self.recorder.record(
            "degraded", tick=tick, site=site,
            block_cap=self._block_cap, admit_cap=self._admit_cap,
        )

    def _note_clean_dispatch(self, tick: int) -> None:
        """Recovery probe: after ``degrade_recover_ticks`` consecutive
        clean decode dispatches, re-escalate one notch (block ladder
        up one power of two, admission cap up one slot) — degradation
        is a pressure response, not a ratchet."""
        if not self.degraded:
            return
        self._ok_ticks += 1
        if self._ok_ticks < self._degrade_recover_ticks:
            return
        self._ok_ticks = 0
        self._block_cap = min(self.decode_block, self._block_cap * 2)
        self._admit_cap = min(self.pool.num_slots, self._admit_cap + 1)
        self.metrics.set_degraded(self.degraded)
        self.recorder.record(
            "recovered" if not self.degraded else "re_escalated",
            tick=tick, block_cap=self._block_cap,
            admit_cap=self._admit_cap,
        )

    def _token_ok(self, token: int) -> bool:
        """Token-stream sanity: device-sampled greedy tokens are argmax
        indices, so they are non-negative and < vocab — anything else
        is corruption (e.g. an injected poison) and quarantines the
        request before it can reach results or the KV frontier."""
        if token < 0:
            return False
        return self._vocab is None or token < int(self._vocab)

    def _quarantine_slot(self, slot: int, tick: int,
                         reason: str) -> RequestResult:
        """Retire one ACTIVE request as ``"failed"``: the slot frees
        (device live mask forced dead, position zeroed — the row emits
        pads and reads no KV until re-leased) and the engine keeps
        serving everyone else."""
        res = self._sched.fail(slot, tick)
        self.metrics.record_quarantine()
        span = self._spans.get(res.id)
        if span is not None:
            span.event("quarantined", tick=tick, slot=slot,
                       reason=reason)
        self.recorder.record(
            "quarantine", tick=tick, id=res.id, slot=slot, reason=reason,
        )
        return res

    def _quarantine_unactivated(self, req, slot: int, tick: int,
                                reason: str) -> RequestResult:
        """Retire a request whose prefill never succeeded (lease still
        held by the admit loop) as ``"failed"``."""
        self.pool.free(slot)
        res = self._sched.fail_unactivated(req, tick)
        self.metrics.record_quarantine()
        span = self._spans.get(req.id)
        if span is not None:
            span.event("quarantined", tick=tick, slot=slot,
                       reason=reason)
        self.recorder.record(
            "quarantine", tick=tick, id=req.id, slot=slot, reason=reason,
        )
        return res

    # -- introspection -----------------------------------------------------

    @property
    def tick(self) -> int:
        return self._sched.tick_count

    @property
    def queue_depth(self) -> int:
        return self._sched.queue_depth

    @property
    def busy(self) -> bool:
        return self._sched.busy

    @property
    def decode_compile_count(self) -> int:
        """How many DISTINCT XLA programs the fused decode block has
        compiled — one per ladder size actually run, never more than
        ``num_decode_blocks`` for the life of the engine (asserted in
        tests; the retrace watchdog logs any violation live with the
        triggering shapes). Scan iterations do NOT count: a T=32 block
        is one program, not 32."""
        return jit_cache_size(self._decode)

    @property
    def prefill_compile_count(self) -> int:
        """How many prefill programs have compiled — bounded by
        ``num_prefill_buckets`` for the life of the engine (asserted in
        tests), however many distinct prompt lengths arrive. With
        chunked prefill every fill runs through the chunk program, so
        the count (and its ``num_chunk_buckets`` ceiling) is the chunk
        ladder's."""
        if self._prefill_chunk is not None:
            return jit_cache_size(self._chunk)
        return jit_cache_size(self._prefill)

    @property
    def resume_compile_count(self) -> int:
        """How many prefix-resume programs have compiled — keyed by the
        REMAINDER bucket, so bounded by ``num_prefill_buckets`` like
        full prefill; 0 without the prefix cache."""
        if self._resume is None:
            return 0
        return jit_cache_size(self._resume)

    # -- public API --------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *,
               eos_id: int | None = None,
               deadline_ticks: int | None = None,
               trace_id: str | None = None) -> int:
        """Queue one request; returns its id. Raises
        :class:`FriendlyError` on invalid budgets or a full queue
        (admission control) — never a bare KeyError/ValueError.

        ``deadline_ticks``: the request must FINISH within that many
        scheduler ticks of submission or it expires (queued or
        mid-decode), surfacing as status ``"expired"``.

        ``trace_id``: fleet-wide trace-context id stamped on the
        request's span and every hand-off payload derived from it
        (docs/OBSERVABILITY.md "Distributed tracing"); supervisors
        pass their global id here so one request's fragments across
        replicas stay joinable. Default: the engine mints
        ``t{request_id}``.
        """
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise FriendlyError(
                f"prompt must be a non-empty 1-D token vector, got "
                f"shape {prompt.shape} (the engine serves one request "
                "per submit; batch by submitting several)"
            )
        if max_new_tokens < 1:
            raise FriendlyError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if int(prompt.size) >= self.cache_len:
            # pointed admission error BEFORE the generic budget check:
            # a prompt this long can never fit a single generated token
            # in the slot buffers, whatever the budget
            raise FriendlyError(
                f"prompt length ({prompt.size}) must be < the engine's "
                f"cache_len ({self.cache_len}); truncate the prompt or "
                "build the engine with a larger cache_len"
            )
        if self._vocab is not None and prompt.size:
            lo, hi = int(prompt.min()), int(prompt.max())
            if lo < 0 or hi >= int(self._vocab):
                raise FriendlyError(
                    f"prompt tokens must be in [0, {self._vocab}) for "
                    f"'{self.graph.name}', got range [{lo}, {hi}]"
                )
        total = int(prompt.size) + max_new_tokens
        if self._block_len:
            # the last block is denoised whole, past the budget's end
            total = -(-total // self._block_len) * self._block_len
        if total > self.cache_len:
            raise FriendlyError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the engine's cache_len "
                f"({self.cache_len}); shorten the request or build the "
                "engine with a larger cache_len"
            )
        if deadline_ticks is not None and deadline_ticks < 1:
            raise FriendlyError(
                f"deadline_ticks must be >= 1, got {deadline_ticks}"
            )
        req = ServeRequest(
            id=self._next_id,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            eos_id=eos_id,
            deadline_tick=(
                self.tick + deadline_ticks
                if deadline_ticks is not None else None
            ),
            submit_tick=self.tick,
            submit_wall=time.perf_counter(),
            trace_id=trace_id or f"t{self._next_id}",
        )
        try:
            self._sched.enqueue(req)
        except FriendlyError:
            self.metrics.record_reject()
            self.recorder.record(
                "rejected", tick=self.tick, prompt_len=int(prompt.size),
                reason="queue_full",
            )
            raise
        self._next_id += 1
        self.metrics.record_submit()
        span = self._tracer.span(
            "request", tick=self.tick, id=req.id, trace=req.trace_id,
            prompt_len=int(prompt.size), max_new_tokens=max_new_tokens,
        )
        span.event("queued", tick=self.tick, queue_depth=self.queue_depth)
        self._spans[req.id] = span
        return req.id

    def step(self) -> list[RequestResult]:
        """One scheduler tick: expire deadlines, admit queued requests
        into free slots (prefill per joiner), ONE fused decode block of
        up to ``decode_block`` tokens for all active slots, retire
        finished sequences. Admission and retirement happen at block
        boundaries; the single host sync per tick fetches the whole
        ``(S, T)`` token block plus the finished vector. Returns the
        requests that reached a terminal state this tick.

        An :class:`EngineKilled` escaping the tick (the simulated
        process crash) first PARKS the device resources
        deterministically — every leased slot returns to the pool, a
        paged pool's page mappings release — so a supervisor that
        restores this engine's snapshot in the same process never
        double-holds pages; the dead engine then refuses further
        steps."""
        if self._dead:
            raise FriendlyError(
                "this engine was killed (EngineKilled) and its device "
                "resources parked; rebuild it with "
                "ServeEngine.restore(snapshot, ...) instead of "
                "stepping it again"
            )
        try:
            return self._step_inner()
        except EngineKilled:
            self._park_after_kill()
            raise

    def _step_inner(self) -> list[RequestResult]:
        tick = self._sched.tick_count
        with self._tracer.region("serve.tick", tick=tick) as timed:
            return self._tick(tick, timed.t0)

    def _tick(self, tick: int, t0: float) -> list[RequestResult]:
        """The body of one tick, which began at ``t0`` (monotonic)."""
        finished = self._sched.expire(tick)
        tokens_this_tick = 0

        # SLO load shedding: while the monitor's budget burns, NEW
        # admissions stop (in-flight requests keep decoding, so the
        # overload actually drains). An IDLE engine admits regardless —
        # with nothing in flight, shedding could never observe recovery
        # and would deadlock the queue.
        shedding = (
            self._slo is not None and self._slo.should_shed
            and self.pool.leased_count > 0
        )
        if shedding and self._sched.queue_depth:
            self.metrics.record_slo_shed()
            self.recorder.record(
                "slo_shed", tick=tick,
                queue_depth=self._sched.queue_depth,
            )

        with self._tracer.region("serve.admit", tick=tick) as admit:
            admitted = 0
            while (
                not shedding
                and self._sched.queue_depth
                and self.pool.free_count
                # admission cap: memory-pressure degradation admits
                # fewer concurrent requests than the pool has slots
                and self.pool.leased_count < self._admit_cap
                and (self._max_fills is None
                     or len(self._sched.filling) < self._max_fills)
            ):
                head = self._sched.queue[0]
                if (self._prefill_chunk is not None
                        and head.id not in self._handoffs):
                    # chunked prefill: the admit loop only STARTS the
                    # fill; the request's ``serve.admit_one`` is the
                    # final chunk, in ``_advance_fills``
                    self._admit_one(tick, finished)
                else:
                    with self._tracer.region(
                        "serve.admit_one", tick=tick, request=head.id,
                        prompt_len=len(head.prompt) + len(head.prefix),
                    ) as one:
                        tokens_this_tick += self._admit_one(
                            tick, finished, one
                        )
                admitted += 1
            if self._sched.filling:
                tokens_this_tick += self._advance_fills(tick, finished)
            admit.count(admitted=admitted)

        # slot occupancy AS OF the decode dispatch: with fused blocks a
        # request can join and retire inside one tick, so sampling after
        # retirement would report empty slots that were busy all block
        leased_this_tick = self.pool.leased_count

        if self._async_host:
            tokens_this_tick += self._decode_phase_async(tick, finished)
        elif self._sched.active:
            tokens_this_tick += self._decode_phase(tick, finished)

        self._sched.tick_count += 1
        # the ``tick`` event's interval ends HERE, before the finish feed,
        # the SLO evaluation and a due checkpoint; ``serve.tick`` goes on
        # to the end
        tick_s = time.monotonic() - t0
        self.metrics.sample_tick(
            self._sched.queue_depth, leased_this_tick,
            tick_s, tokens_emitted=tokens_this_tick,
        )
        self.recorder.record(
            "tick", tick=tick, ms=round(tick_s * 1e3, 3),
            tokens=tokens_this_tick,
        )
        for res in finished:
            self.metrics.record_finish(res)
            # a request retired before admission (deadline expiry)
            # abandons any pending hand-off payload
            self._handoffs.pop(res.id, None)
            span = self._spans.pop(res.id, None)
            if span is not None:
                span.end(res.status, tick=res.finish_tick,
                         generated=res.generated)
        # SLO evaluation once per tick, AFTER the finish feed: next
        # tick's admission sees the freshest shed signal
        if self._slo is not None:
            self._slo.evaluate(tick=tick)
        # periodic snapshot cadence (docs/SERVING.md "Replicated
        # serving"): refresh the recovery point every N completed ticks
        # — a shorter cadence re-decodes less after failover, a longer
        # one checkpoints less often
        if (
            self._snapshot_every is not None
            and self._sched.tick_count % self._snapshot_every == 0
        ):
            self.checkpoint()
        return finished

    def _admit_one(self, tick: int, finished: list, region=None) -> int:
        """Bring the queue's next request into a leased slot: adopt a
        KV hand-off, start a chunked fill, or prefill (a prefix hit
        resumes the remainder) and activate. A request that cannot be
        brought in is quarantined alone, into ``finished``. ``region``
        is the ``serve.admit_one`` this runs under, where it does.
        Returns the first tokens emitted (0 or 1)."""
        if self._block_len:
            return self._admit_block(tick, finished, region)
        req = self._sched.pop_next()
        slot = self.pool.lease()
        if region is not None:
            region.count(slot=slot)
        span = self._spans.get(req.id)
        if span is not None:
            span.event("admitted", tick=tick, slot=slot)
        # preempted/restored requests re-prefill prompt + the
        # tokens already emitted: greedy determinism makes the
        # resumed stream bit-identical to an uninterrupted one
        seq = (
            np.concatenate([req.prompt, req.prefix])
            if len(req.prefix) else req.prompt
        )
        first = None
        attempts = 0
        # cross-replica KV hand-off adoption (serve/fleet.py):
        # the payload's cache is another replica's prefill
        # program output for this EXACT sequence, so a direct
        # write into the leased slot is bit-identical to
        # running prefill here — no forward pass, no XLA
        # program. The write travels the ``serve.handoff``
        # fault hook; a payload that cannot land falls back to
        # the full local prefill below (greedy determinism
        # keeps the resulting stream bit-identical).
        payload = self._handoffs.pop(req.id, None)
        adopted = False
        if payload is not None and self._faults is not None:
            # the serve.handoff silent-corruption drill: a
            # seeded bit-flip in one KV leaf between production
            # and adoption
            cseed = self._faults.corrupt_spec(
                "serve.handoff", tick=tick, request=req.id,
                replica=self._replica,
            )
            if cseed is not None:
                payload = integrity.corrupt_payload(payload,
                                                    cseed)
        if payload is not None:
            ok, expected, actual = integrity.verify_payload(
                payload
            )
            if not ok:
                # checksum mismatch: the payload is untrusted —
                # discard it and rebuild the same KV from the
                # prompt via the full-prefill path below
                # (greedy determinism keeps the stream
                # bit-identical)
                self.metrics.record_integrity_handoff_failure()
                self.recorder.record(
                    "integrity.handoff_checksum", tick=tick,
                    id=req.id, expected=expected, actual=actual,
                )
                self.metrics.record_handoff_fallback()
                self.recorder.record(
                    "handoff_fallback", tick=tick, id=req.id,
                )
                payload = None
        p = len(seq)
        if payload is not None:
            bucket = self.prefill_bucket(p)
            cache = payload["kv"]
            with self._tracer.region("serve.handoff", tick=tick,
                                     request=req.id) as timed:
                while True:
                    try:
                        if self._faults is not None:
                            self._faults.fire(
                                "serve.handoff", tick=tick,
                                request=req.id,
                                replica=self._replica,
                            )
                        self._pool_write(req.id, slot, cache, p)
                        if self._prefix_cache:
                            self.pool.prefix_insert(slot, seq)
                        first = int(payload["first_token"])
                        adopted = True
                        break
                    except Exception as e:
                        if is_resource_exhausted(e):
                            self._note_oom(tick,
                                           "serve.handoff")
                        elif not is_transient(e):
                            raise
                        attempts += 1
                        if attempts > self._retry_limit:
                            break
                        self._backoff(attempts)
            if not adopted:
                # lost/undeliverable hand-off: the request
                # stays, the payload is discarded, and the
                # full-prefill path below rebuilds the same
                # KV from the prompt (attempts carry over
                # into its retry budget)
                self.metrics.record_handoff_fallback()
                self.recorder.record(
                    "handoff_fallback", tick=tick, id=req.id,
                )
        if not adopted and self._prefill_chunk is not None:
            # chunked prefill: admission only STARTS the fill
            # (prefix probe + carry allocation — no forward
            # pass); _advance_fills below dispatches bounded
            # chunk windows, one per tick per fill, so a long
            # prompt can never monopolize a tick. A fill no
            # wider than one chunk still completes on its
            # admission tick — short-prompt TTFT is unchanged.
            self._start_fill(req, slot, seq, tick)
            return 0
        # prefix-cache probe: a hit swaps the full-prompt
        # prefill for a REMAINDER resume against the cached
        # prefix's pages (shared, refcounted — the prefix
        # prefilled once, ever)
        hit = (
            self.pool.prefix_lookup(
                seq, self.prefill_bucket, slot=slot
            )
            if self._prefix_cache and not adopted else None
        )
        keep = 0
        if hit is not None:
            entry, keep = hit
            r = p - keep
            bucket = self.prefill_bucket(r)
            padded = np.full((bucket,), self.pad_id, np.int32)
            padded[:r] = seq[keep:]
            # the resume input: the prefix's K/V gathered back into a
            # linear cache (an eager page read, no donation — retries
            # reuse it)
            lin = self.pool.gather_prefix(entry, keep)
            family = f"resume[{bucket}]"
            if self.metrics.perf.wants_program(family):
                self.metrics.perf.register_program(
                    family,
                    analyze_jit_cost(
                        self._resume._fn._fn, self.variables,
                        padded[None], lin, keep, r - 1,
                    ),
                )
            with self._tracer.region("serve.prefill", tick=tick,
                                     request=req.id,
                                     bucket=bucket) as timed:
                while True:
                    try:
                        if self._faults is not None:
                            self._faults.fire(
                                "serve.prefill", tick=tick,
                                request=req.id, replica=self._replica,
                            )
                        with self._tracer.region(
                            "serve.prefill_dispatch", request=req.id
                        ):
                            first_d, cache = self._resume(
                                self.variables,
                                jnp.asarray(padded[None]), lin,
                                keep, r - 1,
                            )
                        # map the shared pages FIRST (the slot's
                        # references keep them alive through any
                        # eviction the remainder write triggers), then
                        # scatter only the remainder [keep, p)
                        if not self.pool.map_prefix(slot, entry, keep):
                            # entry evicted since the lookup (a prior
                            # attempt's own page pressure): its pages
                            # may already be free or reallocated, so
                            # the remainder cache cannot seed the slot
                            # — fall back to the full prefill below
                            hit = None
                            keep = 0
                            break
                        self._pool_write(req.id, slot, cache, p,
                                         start=keep)
                        first = self._first_token(req.id, first_d)
                        break
                    except Exception as e:
                        if is_resource_exhausted(e):
                            self._note_oom(tick, "serve.prefill")
                        elif not is_transient(e):
                            raise
                        attempts += 1
                        if attempts > self._retry_limit:
                            break
                        self._backoff(attempts)
        if hit is None and not adopted:
            # the miss path — also the landing spot for a stale-prefix
            # fallback above and a failed hand-off adoption (attempts
            # carry over into this loop's retry budget)
            bucket = self.prefill_bucket(p)
            padded = np.full((bucket,), self.pad_id, np.int32)
            padded[:p] = seq
            # device analytics: analyze each prefill bucket's program
            # ONCE, from abstract shapes — lowering only, no backend
            # compile, no device work, so the prefill_compile_count pin
            # is untouched
            family = f"prefill[{bucket}]"
            if self.metrics.perf.wants_program(family):
                self.metrics.perf.register_program(
                    family,
                    analyze_jit_cost(
                        self._prefill._fn._fn,
                        self.variables, padded[None], p - 1,
                    ),
                )
            with self._tracer.region("serve.prefill", tick=tick,
                                     request=req.id,
                                     bucket=bucket) as timed:
                while True:
                    try:
                        if self._faults is not None:
                            self._faults.fire(
                                "serve.prefill", tick=tick,
                                request=req.id, replica=self._replica,
                            )
                        with self._tracer.region(
                            "serve.prefill_dispatch", request=req.id
                        ):
                            first_d, cache, *stats = self._prefill(
                                self.variables,
                                jnp.asarray(padded[None]), p - 1,
                            )
                        # only the REAL prompt's K/V enter the slot;
                        # the pad tail of the bucket cache is dropped
                        # here
                        self._pool_write(req.id, slot, cache, p)
                        if self._prefix_cache:
                            self.pool.prefix_insert(slot, seq)
                        first = self._first_token(req.id, first_d,
                                                  timed, stats)
                        break
                    except Exception as e:
                        if is_resource_exhausted(e):
                            self._note_oom(tick, "serve.prefill")
                        elif not is_transient(e):
                            raise
                        attempts += 1
                        if attempts > self._retry_limit:
                            break
                        self._backoff(attempts)
        if first is None:
            # retries exhausted: quarantine THIS request only —
            # the admit loop moves on to the next joiner
            finished.append(self._quarantine_unactivated(
                req, slot, tick, "prefill_failed"
            ))
            return 0
        if self._faults is not None:
            poison = self._faults.poison_value(
                "serve.handoff" if adopted else "serve.prefill",
                tick=tick, request=req.id,
                replica=self._replica,
            )
            if poison is not None:
                first = int(poison)
        # the interval of the hand-off or prefill region that landed the
        # first token: the retry loop, ending at prefill's EXISTING host
        # sync
        prefill_s = timed.ms / 1e3
        if adopted:
            # no program ran: the KV landed by direct write, so
            # nothing feeds the dispatch analytics — the event
            # timeline records the adoption instead
            self.metrics.record_handoff_adopt()
            if span is not None:
                span.event(
                    "handoff_adopted", tick=tick, seq_len=p,
                    ms=round(prefill_s * 1e3, 3),
                )
            self.recorder.record(
                "handoff_adopted", tick=tick, id=req.id,
                seq_len=p, ms=round(prefill_s * 1e3, 3),
            )
        else:
            if span is not None:
                span.event(
                    "prefill", tick=tick, bucket=bucket,
                    ms=round(prefill_s * 1e3, 3), reused=keep,
                )
            # the dispatch interval ends at prefill's EXISTING
            # host sync (int(first_d[0]) above) — analytics
            # adds none of its own
            self.metrics.perf.record_dispatch(
                family, prefill_s, tokens=1
            )
            self.recorder.record(
                "dispatch", tick=tick, family=family,
                ms=round(prefill_s * 1e3, 3), tokens=1,
            )
        if not self._token_ok(first):
            # corrupted first token: quarantine before it can
            # enter results or seed the decode frontier
            finished.append(self._quarantine_unactivated(
                req, slot, tick, "poisoned_token"
            ))
            return 0
        self.metrics.record_first_token(
            req, tick, bucket=None if adopted else bucket
        )
        if self.role == "prefill" and not (
            len(req.prefix) + 1 >= req.max_new_tokens
            or (req.eos_id is not None and first == req.eos_id)
        ):
            # prefill-role terminal (docs/SERVING.md
            # "Disaggregated fleet"): the slot's work is done —
            # the raw prefill/resume output cache (rows [0, p)
            # valid) and the first token ship to a decode
            # replica via the outbox. The slot frees; under a
            # prefix cache the inserted entry keeps the pages
            # alive for future local hits. A request the first
            # token already FINISHES (budget or EOS) skips the
            # hand-off and completes here via activate below.
            self.pool.free(slot)
            payload = {
                "id": req.id,
                "prompt": np.asarray(req.prompt, np.int32),
                "prefix": np.asarray(req.prefix, np.int32),
                "length": p,
                "first_token": int(first),
                "kv": cache,
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                # trace context rides the hand-off: the decode
                # replica's span carries the SAME id, which is
                # what lets the hub draw the prefill->decode
                # flow arrow (checksum covers only the
                # integrity-bearing fields, so this is free)
                "trace_id": req.trace_id,
            }
            # stamped at PRODUCTION: the adopting replica
            # re-hashes before writing the cache into a slot,
            # so wire/at-rest corruption downgrades to the
            # full-local-prefill fallback instead of silently
            # poisoning a stream (docs/SERVING.md)
            payload["checksum"] = integrity.payload_checksum(
                payload
            )
            self._outbox.append(payload)
            self.recorder.record(
                "handoff_out", tick=tick, id=req.id, seq_len=p,
                trace=req.trace_id,
            )
            finished.append(
                self._sched.handoff_result(req, first, tick)
            )
            return 1
        done = self._sched.activate(slot, req, first, tick)
        if done is not None:
            finished.append(done)
        return 1

    def _admit_block(self, tick: int, finished: list, region=None) -> int:
        """:meth:`_admit_one` for a model that generates by diffusion
        over blocks: the prompt's (and a resume prefix's) WHOLE blocks
        through the block-causal prefill into the leased slot, and what
        is left of it inside a block kept as the committed start of the
        slot's first block. No token comes of an admission, so the host
        waits for nothing here: the prefill runs ahead of the next
        denoising program. The ``request`` span's ``prefill`` event still
        marks the prefill's end on the host, with its bucket. Returns
        0."""
        req = self._sched.pop_next()
        slot = self.pool.lease()
        if region is not None:
            region.count(slot=slot)
        span = self._spans.get(req.id)
        if span is not None:
            span.event("admitted", tick=tick, slot=slot)
        seq = (np.concatenate([req.prompt, req.prefix])
               if len(req.prefix) else req.prompt)
        start = len(seq) // self._block_len * self._block_len
        bucket = self.prefill_bucket(start)
        padded = np.full((bucket,), self.pad_id, np.int32)
        padded[:start] = seq[:start]
        family = f"prefill[{bucket}]"
        attempts, landed = 0, False
        with self._tracer.region("serve.prefill", tick=tick, request=req.id,
                                 bucket=bucket) as timed:
            while True:
                try:
                    if self._faults is not None:
                        self._faults.fire("serve.prefill", tick=tick,
                                          request=req.id,
                                          replica=self._replica)
                    with self._tracer.region("serve.prefill_dispatch",
                                             request=req.id):
                        cache = self._prefill(
                            self.variables, jnp.asarray(padded[None]),
                            np.int32(start))
                    self._pool_write(req.id, slot, cache, start)
                    landed = True
                    break
                except Exception as e:
                    if is_resource_exhausted(e):
                        self._note_oom(tick, "serve.prefill")
                    elif not is_transient(e):
                        raise
                    attempts += 1
                    if attempts > self._retry_limit:
                        break
                    self._backoff(attempts)
        if not landed:
            finished.append(self._quarantine_unactivated(
                req, slot, tick, "prefill_failed"))
            return 0
        ms = round(timed.ms, 3)
        if span is not None:
            span.event("prefill", tick=tick, bucket=bucket, ms=ms, reused=0)
        self.metrics.perf.record_dispatch(family, timed.ms / 1e3, tokens=0)
        self.recorder.record("dispatch", tick=tick, family=family, ms=ms,
                             tokens=0)
        self._sched.activate_block(slot, req, start, seq[start:], tick)
        return 0

    def _pool_write(self, request: int, slot: int, cache: dict,
                    length: int, start: int = 0) -> None:
        """``pool.write_prefill`` as the region ``serve.pool_write``,
        with the pool's own count of what the write launched."""
        with self._tracer.region("serve.pool_write",
                                 request=request) as r:
            dispatches, nbytes = self.pool.write_prefill(
                slot, cache, length, start=start
            )
            by_kind = getattr(self.pool, "bytes_by_kind", None)
            r.count(dispatches=dispatches, bytes=nbytes,
                    **(by_kind(length, start) if by_kind else {}))

    def _first_token(self, request: int, first_d, prefill=None,
                     stats=()) -> int:
        """The host's wait for a prefill's first token: the admit
        path's one sync. A prefill that routed tokens to experts hands
        its counters over in ``stats``; they come back in the same fetch
        and are counted on ``prefill``, its ``serve.prefill`` region."""
        with self._tracer.region("serve.first_token", request=request):
            if not stats:
                return int(first_d[0])
            first_h, stats_h = jax.device_get((first_d, stats[0]))
            prefill.count(**_routing_attrs(stats_h, 1))
            return int(first_h[0])

    # -- chunked prefill (docs/SERVING.md "Chunked prefill") ---------------

    def _fresh_carry(self) -> dict:
        """A zeroed batch-1 linear cache spanning the FULL cache_len —
        the chunked fill's carry: every chunk program reads and extends
        it, and its fixed shape keeps chunk programs keyed by the chunk
        bucket alone. Committed REPLICATED under a mesh (mirroring
        ``gather_prefix``) so the chunk jit sees one signature per
        bucket."""
        cache = init_cache(self.graph, self.variables, 1, self.cache_len)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            cache = jax.device_put(
                cache, NamedSharding(self.mesh, PartitionSpec())
            )
        return cache

    def _start_fill(self, req, slot: int, seq, tick: int) -> None:
        """Begin a chunked fill in a freshly leased slot: probe the
        prefix cache (a hit seeds the carry with the shared prefix,
        gathered once) and register the fill frontier with the
        scheduler. No forward pass runs here — ``_advance_fills`` owns
        every chunk dispatch."""
        total = len(seq)
        keep = 0
        entry = None
        hit = (
            self.pool.prefix_lookup(seq, self.chunk_bucket, slot=slot)
            if self._prefix_cache else None
        )
        if hit is not None:
            entry, keep = hit
            carry = self.pool.gather_prefix(entry, keep)
        else:
            carry = self._fresh_carry()
        self._sched.start_fill(
            slot, req, total, keep, {"cache": carry, "entry": entry},
            tick,
        )
        span = self._spans.get(req.id)
        if span is not None:
            span.event("fill_started", tick=tick, total=total,
                       reused=keep)

    def _advance_fills(self, tick: int, finished: list) -> int:
        """Advance every mid-fill slot by ONE bounded chunk dispatch.
        Intermediate chunks are exactly ``prefill_chunk`` wide and
        chain asynchronously (no host sync — the next chunk's inputs
        are the previous chunk's in-flight outputs); a fill's FINAL
        chunk pads to its ladder bucket, lands the carry in the slot
        via ``write_prefill(start=keep)`` and pays the fill's one host
        sync for the first token. Bit-identical to monolithic prefill:
        the chunks recompute the same K/V at the same positions from
        the same tokens, and the final logits slice reads the true
        last-token position. Returns the first tokens emitted by fills
        that completed this tick."""
        tokens = 0
        for slot in sorted(self._sched.filling):
            fs = self._sched.filling[slot]
            if fs.total - fs.filled <= self._prefill_chunk:
                # the fill's FINAL chunk lands the request: with chunked
                # prefill this, not the admit loop, is where one request
                # is written into the pool, waited for and activated
                with self._tracer.region(
                    "serve.admit_one", tick=tick, request=fs.req.id,
                    slot=slot, prompt_len=fs.total,
                ):
                    tokens += self._advance_fill(slot, fs, tick, finished)
            else:
                tokens += self._advance_fill(slot, fs, tick, finished)
        return tokens

    def _advance_fill(self, slot: int, fs, tick: int,
                      finished: list) -> int:
        """One chunk of one fill; the first tokens it emitted (0 or 1)."""
        req = fs.req
        seq = (
            np.concatenate([req.prompt, req.prefix])
            if len(req.prefix) else req.prompt
        )
        r = fs.total - fs.filled
        final = r <= self._prefill_chunk
        if final:
            bucket = self.chunk_bucket(r)
            # final-chunk WINDOW TRICK: the padded bucket window
            # must not overflow cache_len (a clamped
            # dynamic_update_slice would corrupt earlier carry
            # positions), so slide its start down and RECOMPUTE the
            # overlap [start, filled) — same tokens at the same
            # positions against the same carry prefix produce
            # identical K/V, so the overwrite is a no-op by value
            # and the program width stays on the ladder
            start = min(fs.filled, self.cache_len - bucket)
            width = bucket
            padded = np.full((bucket,), self.pad_id, np.int32)
            padded[: fs.total - start] = seq[start:fs.total]
            last = (fs.total - 1) - start
        else:
            start = fs.filled
            width = self._prefill_chunk
            padded = np.ascontiguousarray(
                seq[start:start + width], dtype=np.int32
            )
            last = width - 1
        family = f"chunk[{width}]"
        if self.metrics.perf.wants_program(family):
            self.metrics.perf.register_program(
                family,
                analyze_jit_cost(
                    self._chunk._fn._fn, self.variables,
                    padded[None], fs.carry["cache"], start, last,
                ),
            )
        attempts = 0
        if not final:
            ok = False
            with self._tracer.region("serve.prefill", tick=tick,
                                     request=req.id,
                                     bucket=width) as timed:
                timed.count(**sparse_prefill_rows(
                    self.graph, fs.carry["cache"], start, width))
                while True:
                    try:
                        if self._faults is not None:
                            self._faults.fire(
                                "serve.prefill", tick=tick,
                                request=req.id,
                                replica=self._replica,
                            )
                        with self._tracer.region(
                            "serve.prefill_dispatch", request=req.id
                        ):
                            _tok_d, cache = self._chunk(
                                self.variables,
                                jnp.asarray(padded[None]),
                                fs.carry["cache"], start, last,
                            )
                        # the chunk program is NOT donated: the old
                        # carry survives until this rebind, so a
                        # faulted dispatch retries on intact state
                        fs.carry["cache"] = cache
                        ok = True
                        break
                    except Exception as e:
                        if is_resource_exhausted(e):
                            self._note_oom(tick, "serve.prefill")
                        elif not is_transient(e):
                            raise
                        attempts += 1
                        if attempts > self._retry_limit:
                            break
                        self._backoff(attempts)
            if not ok:
                self._sched.fill_done(slot)
                finished.append(self._quarantine_unactivated(
                    req, slot, tick, "prefill_failed"
                ))
                return 0
            fs.filled += width
            chunk_s = timed.ms / 1e3
            self.metrics.record_prefill_chunk()
            # no host sync here — the measured interval is
            # enqueue-side only; device-time attribution rides the
            # final chunk's sync
            self.metrics.perf.record_dispatch(family, chunk_s)
            self.recorder.record(
                "prefill_chunk", tick=tick, id=req.id,
                filled=fs.filled, total=fs.total,
                ms=round(chunk_s * 1e3, 3),
            )
            span = self._spans.get(req.id)
            if span is not None:
                span.event("prefill_chunk", tick=tick,
                           filled=fs.filled, total=fs.total)
            return 0

        # -- final chunk: compute, land in the slot, sync ----------
        entry = fs.carry.get("entry")
        first = None
        stale = False
        with self._tracer.region("serve.prefill", tick=tick,
                                 request=req.id, bucket=width) as timed:
            timed.count(**sparse_prefill_rows(
                self.graph, fs.carry["cache"], start, width))
            while True:
                try:
                    if self._faults is not None:
                        self._faults.fire(
                            "serve.prefill", tick=tick,
                            request=req.id, replica=self._replica,
                        )
                    with self._tracer.region(
                        "serve.prefill_dispatch", request=req.id
                    ):
                        first_d, cache = self._chunk(
                            self.variables, jnp.asarray(padded[None]),
                            fs.carry["cache"], start, last,
                        )
                    # map the shared prefix pages FIRST (as the
                    # monolithic resume path does), then scatter
                    # only [keep, total)
                    if entry is not None and not self.pool.map_prefix(
                        slot, entry, fs.keep
                    ):
                        stale = True
                        break
                    self._pool_write(req.id, slot, cache, fs.total,
                                     start=fs.keep)
                    fs.carry["cache"] = cache
                    first = self._first_token(req.id, first_d)
                    break
                except Exception as e:
                    if is_resource_exhausted(e):
                        self._note_oom(tick, "serve.prefill")
                    elif not is_transient(e):
                        raise
                    attempts += 1
                    if attempts > self._retry_limit:
                        break
                    self._backoff(attempts)
        if stale:
            # the prefix entry evicted since the fill started: the
            # slot can no longer map pages for [0, keep), so the
            # fill restarts from scratch — the chunked analog of
            # the monolithic stale-hit full-prefill fallback, and
            # equally deterministic (the eventual stream is
            # unchanged)
            fs.filled = 0
            fs.keep = 0
            fs.carry = {"cache": self._fresh_carry(), "entry": None}
            return 0
        if first is None:
            self._sched.fill_done(slot)
            finished.append(self._quarantine_unactivated(
                req, slot, tick, "prefill_failed"
            ))
            return 0
        fs.filled = fs.total
        chunk_s = timed.ms / 1e3
        self.metrics.record_prefill_chunk()
        if self._faults is not None:
            poison = self._faults.poison_value(
                "serve.prefill", tick=tick, request=req.id,
                replica=self._replica,
            )
            if poison is not None:
                first = int(poison)
        if self._prefix_cache and entry is None:
            self.pool.prefix_insert(slot, seq)
        self._sched.fill_done(slot)
        span = self._spans.get(req.id)
        if span is not None:
            span.event(
                "prefill", tick=tick, bucket=bucket,
                ms=round(chunk_s * 1e3, 3), reused=fs.keep,
            )
        self.metrics.perf.record_dispatch(family, chunk_s, tokens=1)
        self.recorder.record(
            "dispatch", tick=tick, family=family,
            ms=round(chunk_s * 1e3, 3), tokens=1,
        )
        if not self._token_ok(first):
            finished.append(self._quarantine_unactivated(
                req, slot, tick, "poisoned_token"
            ))
            return 0
        self.metrics.record_first_token(req, tick, bucket=bucket)
        if self.role == "prefill" and not (
            len(req.prefix) + 1 >= req.max_new_tokens
            or (req.eos_id is not None and first == req.eos_id)
        ):
            # prefill-role hand-off fires at FILL COMPLETION: the
            # carry's rows [0, total) are exactly the monolithic
            # prefill output the payload contract expects
            self.pool.free(slot)
            payload = {
                "id": req.id,
                "prompt": np.asarray(req.prompt, np.int32),
                "prefix": np.asarray(req.prefix, np.int32),
                "length": fs.total,
                "first_token": int(first),
                "kv": fs.carry["cache"],
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                "trace_id": req.trace_id,
            }
            payload["checksum"] = integrity.payload_checksum(
                payload
            )
            self._outbox.append(payload)
            self.recorder.record(
                "handoff_out", tick=tick, id=req.id,
                seq_len=fs.total, trace=req.trace_id,
            )
            finished.append(
                self._sched.handoff_result(req, first, tick)
            )
            return 1
        done = self._sched.activate(slot, req, first, tick)
        if done is not None:
            finished.append(done)
        return 1

    # -- pipelined async host loop (docs/SERVING.md "Async host loop") -----

    def _decode_phase_async(self, tick: int, finished: list) -> int:
        """One PIPELINED decode round: dispatch this tick's block N+1
        behind the in-flight block N, then fetch N's tokens — the host
        bookkeeping between the two (and the whole admit/fill phase
        before them) overlaps into N's device execution. At most one
        host sync per block, exactly as the synchronous loop, but the
        sync lands one tick late and rarely blocks. Token streams are
        bit-identical to the synchronous engine: dispatch inputs are
        derived from device-side state (in-flight last tokens selected
        on device) plus conservative host budget views, and the fetch's
        identity fence drops any row whose slot changed hands after
        dispatch."""
        prev = self._inflight
        self._inflight = None
        status = self._dispatch_block(tick, prev)
        fetched = self._fetch_block(prev, tick)
        with self._tracer.region("serve.retire", tick=tick) as retire:
            before = len(finished)
            n_tokens = self._consume_inflight(prev, fetched, tick,
                                              finished)
            if status == "failed":
                # the batch stayed undispatchable through retries AND
                # degradation — quarantine what is left of it, AFTER
                # the previous block's tokens were committed above
                for slot in list(self._sched.active):
                    finished.append(self._quarantine_slot(
                        slot, tick, "decode_failed"
                    ))
            if self._inflight is not None and not self._sched.busy:
                # every request retired at the fetch above (e.g. EOS
                # swept the batch) while a speculative block is still
                # in flight: drain it now — its rows all fail the
                # identity fence, so it contributes nothing, but run()
                # must not exit with an open deferred-free window
                inf, self._inflight = self._inflight, None
                n_tokens += self._consume_inflight(
                    inf, self._fetch_block(inf, tick), tick, finished
                )
            retire.count(finished=len(finished) - before)
        return n_tokens

    def _dispatch_block(self, tick: int, prev: dict | None) -> str:
        """Dispatch one fused decode block WITHOUT fetching it (async
        mode). Returns ``"ok"`` (in-flight record stored), ``"idle"``
        (nothing to dispatch: no active slots, or every active slot's
        budget may already exhaust inside ``prev``) or ``"failed"``
        (retries exhausted).

        The pipelining contract, input by input:

        * last tokens — the host's view lags for slots riding ``prev``,
          so their rows select ``prev``'s final emitted token ON DEVICE
          (``jnp.where`` over the in-flight output; async, no sync).
        * remaining budgets — reduced by ``prev``'s block size for
          in-flight slots (the conservative view). A slot whose
          adjusted budget is <= 0 either retires at ``prev``'s fetch
          (its rows here are dropped by the identity fence) or was
          going to die on device anyway; the block-size clamp uses only
          POSITIVE adjusted budgets, so no surviving stream can overrun
          its budget mid-block — the same parity rule as the
          synchronous loop.
        * page frontiers — advanced by ``prev``'s block size before
          ``ensure_decode_pages``, covering the writes the in-flight
          block may still land.
        """
        attempts = 0
        while self._sched.active:
            states = dict(self._sched.active)
            lag = {}
            if prev is not None:
                for slot, st in prev["states"].items():
                    if states.get(slot) is st:
                        lag[slot] = prev["t_block"]
            pre_pos = {
                slot: st.pos + lag.get(slot, 0)
                for slot, st in states.items()
            }
            tok, rem, eos, _ = self._sched.decode_block_inputs(
                self.pad_id
            )
            rems = []
            for slot, st in states.items():
                adj = (
                    st.req.max_new_tokens - len(st.out)
                    - lag.get(slot, 0)
                )
                rem[slot] = adj
                if adj > 0:
                    rems.append(adj)
            if not rems:
                return "idle"
            t_block = self._block_size(min(rems))
            slot_sh = None
            if self.mesh is not None:
                slot_sh = self.pool.slot_sharding
                tok_d = jax.device_put(jnp.asarray(tok), slot_sh)
                rem_d = jax.device_put(jnp.asarray(rem), slot_sh)
                eos_d = jax.device_put(jnp.asarray(eos), slot_sh)
            else:
                tok_d, rem_d, eos_d = (
                    jnp.asarray(tok), jnp.asarray(rem), jnp.asarray(eos)
                )
            if lag:
                sel = np.zeros((self.pool.num_slots,), bool)
                for slot in lag:
                    sel[slot] = True
                sel_d = jnp.asarray(sel)
                tok_d = jnp.where(sel_d, prev["toks"][:, -1], tok_d)
                if slot_sh is not None:
                    # re-commit the selected vector so the jit sees the
                    # pinned signature every tick
                    tok_d = jax.device_put(tok_d, slot_sh)
            family = f"decode[T={t_block}]"
            if self.metrics.perf.wants_program(family):
                self.metrics.perf.register_program(
                    family,
                    analyze_jit_cost(
                        self._decode._fn._fn, self.variables,
                        self.pool.buffers, self.pool.positions,
                        self.pool.live, tok_d, rem_d, eos_d, t_block,
                    ),
                )
            try:
                with self._tracer.region("serve.decode", tick=tick,
                                         block=t_block) as issue:
                    if self._paged:
                        self.pool.ensure_decode_pages(pre_pos, t_block)
                    if self._faults is not None:
                        self._faults.fire("serve.decode", tick=tick,
                                          replica=self._replica)
                    # the live vector is DONATED into this dispatch,
                    # but when it is also the in-flight block's fetch
                    # target (prev's output) donation would delete it
                    # before prev's device_get — donate a copy instead
                    # (S bools; async, ordered after prev)
                    live_in = self.pool.live
                    if prev is not None:
                        live_in = jnp.copy(live_in)
                    toks, live, buffers, positions, *stats = self._decode(
                        self.variables, self.pool.buffers,
                        self.pool.positions, live_in,
                        tok_d, rem_d, eos_d, t_block,
                    )
                    self.pool.buffers = buffers
                    self.pool.positions = positions
                    self.pool.live = live
            except Exception as e:
                if is_resource_exhausted(e):
                    self._note_oom(tick, "serve.decode")
                elif not is_transient(e):
                    raise
                attempts += 1
                if attempts > self._retry_limit:
                    return "failed"
                self._backoff(attempts)
                continue
            self._dispatch_gen += 1
            self.pool.defer_frees(self._dispatch_gen)
            self._inflight = {
                "toks": toks, "live": live, "stats": stats,
                "states": states,
                "pre_pos": pre_pos, "t_block": t_block,
                "family": family, "issued": issue.t0,
                "gen": self._dispatch_gen, "tick": tick,
                "n_active": len(states),
                "overlapped": prev is not None,
            }
            if prev is not None:
                self.metrics.record_overlapped_dispatch()
            return "ok"
        return "idle"

    def _fetch_block(self, block: dict | None, tick: int):
        """A dispatched block's ONE host sync, as the region
        ``serve.fetch``, behind its own retry loop (re-dispatching
        would decode past the block and skip its tokens): the ``(S,
        T)`` tokens and the per-slot live vector come back together.
        Returns ``(toks_h, live_h, done)``, ``done`` being the
        monotonic end of the fetch and the arrays None when the
        retries ran out; None for no block."""
        if block is None:
            return None
        toks_h = live_h = None
        attempts = 0
        with self._tracer.region("serve.fetch", tick=tick,
                                 block=block["t_block"]) as fetch:
            while True:
                try:
                    if self._faults is not None:
                        self._faults.fire("serve.device_get", tick=tick,
                                          replica=self._replica)
                    # a routed model's counters ride the same fetch
                    # (an empty list for any other)
                    toks_h, live_h, stats_h = jax.device_get(
                        (block["toks"], block["live"], block["stats"])
                    )
                    block["routing"] = _routing_attrs(
                        stats_h and stats_h[0], block["t_block"],
                        block.get("passes"))
                    break
                except Exception as e:
                    if not (is_transient(e) or is_resource_exhausted(e)):
                        raise
                    attempts += 1
                    if attempts > self._retry_limit:
                        break
                    self._backoff(attempts)
        # the host stood still for the fetch: the numerator the async
        # loop exists to shrink
        self.metrics.record_host_sync(fetch.ms / 1e3)
        return toks_h, live_h, fetch.t1

    def _consume_inflight(self, inflight: dict | None, fetched,
                          tick: int, finished: list) -> int:
        """Consume one fetched block (async mode): the same poison/
        validation/consume/accounting pipeline as the synchronous
        loop — except every row passes the IDENTITY FENCE (the slot
        must still hold the request captured at dispatch) and the
        pools' deferred frees stamped up to this block's generation
        flush afterwards."""
        if inflight is None:
            if self._inflight is None:
                # nothing in flight in either direction: close the
                # deferred-free window so frees turn immediate again
                self.pool.flush_frees(None)
            return 0
        states = inflight["states"]
        pre_pos = inflight["pre_pos"]
        t_block = inflight["t_block"]
        family = inflight["family"]
        n_active = inflight["n_active"]

        def _live_rows():
            return [
                s for s, st in states.items()
                if self._sched.active.get(s) is st
            ]

        toks_h, live_h, done = fetched
        prev_done = self._prev_block_done
        self._prev_block_done = done
        if toks_h is None:
            for slot in _live_rows():
                finished.append(self._quarantine_slot(
                    slot, tick, "device_get_failed"
                ))
            self.pool.flush_frees(inflight["gen"])
            if self._inflight is None:
                self.pool.flush_frees(None)
            return 0

        # queued-vs-executing attribution: a pipelined block could not
        # START before the previous block's outputs materialized (its
        # inputs are that block's donated buffers), so the span from
        # issue to the previous fetch's completion is queue time, not
        # device time — core/perf.py subtracts it from device_s so MFU
        # and bandwidth figures stay honest under pipelining
        dispatch_s = done - inflight["issued"]
        queued_s = 0.0
        if inflight["overlapped"]:
            queued_s = min(
                dispatch_s, max(0.0, prev_done - inflight["issued"])
            )
        toks_h = np.asarray(toks_h)
        if toks_h.ndim == 1:
            toks_h = toks_h[:, None]
        if self._faults is not None:
            toks_h = self._faults.poison_block(
                "serve.device_get", toks_h, tick=tick,
                slots=_live_rows(), replica=self._replica,
            )
        bad_rows = (toks_h < 0).any(axis=1)
        if self._vocab is not None:
            bad_rows |= (toks_h >= int(self._vocab)).any(axis=1)
        quarantined: set[int] = set()
        if bad_rows.any():
            for slot in _live_rows():
                if bad_rows[slot]:
                    finished.append(self._quarantine_slot(
                        slot, tick, "poisoned_token"
                    ))
                    quarantined.add(slot)

        blk_finished, consumed = self._sched.consume(
            toks_h, tick, states=states
        )
        n_tokens = sum(consumed.values())
        live_kv = sum(
            c * (pre_pos[slot] + 1) + c * (c - 1) // 2
            for slot, c in consumed.items()
        )
        exec_s = max(0.0, dispatch_s - queued_s)
        self.metrics.record_decode(
            n_active, exec_s, tokens_emitted=n_tokens,
            block=t_block, live_kv=live_kv, cache_len=self.cache_len,
        )
        self.metrics.perf.record_dispatch(
            family, dispatch_s, tokens=n_tokens, queued_s=queued_s,
        )
        self.recorder.record(
            "dispatch", tick=tick, family=family,
            ms=round(exec_s * 1e3, 3),
            queued_ms=round(queued_s * 1e3, 3), tokens=n_tokens,
            **inflight.get("routing", {}),
        )
        if __debug__:
            # device/host parity holds row by row for every request
            # that kept its slot from dispatch to fetch — rows the
            # identity fence dropped (consume skipped them) and
            # quarantined rows are exempt, mirroring the synchronous
            # loop's quarantine exemption
            for slot, st in states.items():
                if slot in quarantined or consumed.get(slot) is None:
                    continue
                assert bool(live_h[slot]) == (
                    self._sched.active.get(slot) is st
                ), (
                    f"device live mask and host retirement disagree "
                    f"for slot {slot} (async block T={t_block})"
                )
        decode_ms = round(exec_s * 1e3, 3)
        for slot, st in states.items():
            if consumed.get(slot) is None:
                continue
            span = self._spans.get(st.req.id)
            if span is not None:
                span.event("decode", tick=tick, pos=pre_pos[slot],
                           n_active=n_active, block=t_block,
                           tokens=consumed.get(slot, 0),
                           step_ms=decode_ms)
        finished.extend(blk_finished)
        self._note_clean_dispatch(tick)
        self.pool.flush_frees(inflight["gen"])
        if self._inflight is None:
            self.pool.flush_frees(None)
        return n_tokens

    def _decode_phase(self, tick: int, finished: list) -> int:
        """One fused decode BLOCK for all active slots, behind the
        resilience layer: transient dispatch errors retry with capped
        deterministic backoff, RESOURCE_EXHAUSTED degrades (smaller
        ladder block, tighter admission, preemption at the floor) and
        retries, and a dispatch that stays impossible quarantines the
        remaining batch — every request gets a definite terminal status
        instead of wedging ``run()``. Appends terminal results to
        ``finished``; returns the real tokens consumed this tick."""
        if self._block_len:
            return self._denoise_phase(tick, finished)
        attempts = 0
        while self._sched.active:
            n_active = len(self._sched.active)
            states = list(self._sched.active.items())
            # write positions BEFORE the block: consume() advances the
            # host mirrors, and the live-KV accounting below needs the
            # per-slot starting frontier. Rebuilt on every retry: an
            # OOM response may have shrunk the block cap or preempted a
            # slot since the failed attempt.
            pre_pos = {slot: st.pos for slot, st in states}
            tok, rem, eos, min_rem = self._sched.decode_block_inputs(
                self.pad_id
            )
            t_block = self._block_size(min_rem)
            if self.mesh is not None:
                # commit the host-built per-tick vectors to the data
                # axis (device_put: a scatter, NOT a host sync) so every
                # tick presents the decode block one fixed signature
                slot_sh = self.pool.slot_sharding
                tok_d = jax.device_put(jnp.asarray(tok), slot_sh)
                rem_d = jax.device_put(jnp.asarray(rem), slot_sh)
                eos_d = jax.device_put(jnp.asarray(eos), slot_sh)
            else:
                tok_d, rem_d, eos_d = (
                    jnp.asarray(tok), jnp.asarray(rem), jnp.asarray(eos)
                )
            # device analytics: analyze each ladder size's program ONCE
            # from abstract shapes, BEFORE the dispatch donates the pool
            # buffers (ShapeDtypeStruct conversion reads only
            # shape/dtype and keeps no buffer references). Lowering
            # fires no backend compile, so the decode_compile_count pin
            # and the watchdog budget are untouched.
            family = f"decode[T={t_block}]"
            if self.metrics.perf.wants_program(family):
                self.metrics.perf.register_program(
                    family,
                    analyze_jit_cost(
                        self._decode._fn._fn, self.variables,
                        self.pool.buffers, self.pool.positions,
                        self.pool.live, tok_d, rem_d, eos_d, t_block,
                    ),
                )
            try:
                with self._tracer.region("serve.decode", tick=tick,
                                         block=t_block) as issue:
                    # paged pool: pre-map every page this block can
                    # write (the tables are read-only DURING the block,
                    # preserving its one host sync). Page exhaustion
                    # raises RESOURCE_EXHAUSTED inside this try, so it
                    # walks the same ladder as a real allocator OOM —
                    # and the preemption it can trigger FREES pages.
                    if self._paged:
                        self.pool.ensure_decode_pages(pre_pos, t_block)
                    # the fault hook fires BEFORE the dispatch: an
                    # injected failure never consumes the donated
                    # buffers, so retrying with the same pool state is
                    # always safe
                    if self._faults is not None:
                        self._faults.fire("serve.decode", tick=tick,
                                          replica=self._replica)
                    toks, live, buffers, positions, *stats = self._decode(
                        self.variables, self.pool.buffers,
                        self.pool.positions, self.pool.live,
                        tok_d, rem_d, eos_d, t_block,
                    )
                    # the inputs were DONATED: rebind the pool's device
                    # state (buffers AND positions/live) to the block's
                    # outputs before anything can touch stale references
                    self.pool.buffers = buffers
                    self.pool.positions = positions
                    self.pool.live = live
            except Exception as e:
                if is_resource_exhausted(e):
                    self._note_oom(tick, "serve.decode")
                elif not is_transient(e):
                    raise
                attempts += 1
                if attempts > self._retry_limit:
                    # the batch stayed undispatchable through retries
                    # AND degradation: quarantine what is left of it
                    for slot, _st in states:
                        if slot in self._sched.active:
                            finished.append(self._quarantine_slot(
                                slot, tick, "decode_failed"
                            ))
                    return 0
                self._backoff(attempts)
                continue

            # the dispatch SUCCEEDED and the pool is rebound: the sync
            # loop pays its block's full device time in the fetch
            block = {
                "toks": toks, "live": live, "stats": stats,
                "t_block": t_block,
                "states": states, "pre_pos": pre_pos,
                "n_active": n_active, "family": family,
            }
            toks_h, live_h, done = self._fetch_block(block, tick)
            with self._tracer.region("serve.retire", tick=tick) as retire:
                before = len(finished)
                n_tokens = self._consume_block(
                    block, done - issue.t0, toks_h, live_h, tick, finished
                )
                retire.count(finished=len(finished) - before)
            return n_tokens
        return 0

    def _denoise_phase(self, tick: int, finished: list) -> int:
        """:meth:`_decode_phase` for a model that generates by diffusion
        over blocks: ONE denoising program (``models.generate.
        make_denoise_block``) over whole blocks for every active slot, as
        many blocks as the slot that needs the fewest still needs, at
        most ``decode_block`` tokens' worth, behind the same retry and
        degradation. Every slot starts it at a block's start: admission
        happens between dispatches, and a dispatch ends on a block's
        close. Returns the tokens served."""
        length = self._block_len
        attempts = 0
        while self._sched.active:
            states = list(self._sched.active.items())
            pre_pos = {slot: st.pos for slot, st in states}
            tok, masked, rem, need = self._sched.denoise_inputs(
                length, self.pad_id)
            n_blocks = max(1, min(need, self._block_cap // length))
            # every close but the last rides the next block's first step
            steps = n_blocks * self._denoise_steps + 1
            try:
                with self._tracer.region("serve.decode", tick=tick,
                                         block=steps) as issue:
                    if self._faults is not None:
                        self._faults.fire("serve.decode", tick=tick,
                                          replica=self._replica)
                    blocks, live, buffers, positions, counts, *stats = \
                        self._decode(
                            self.variables, self.pool.buffers,
                            self.pool.positions, self.pool.live,
                            jnp.asarray(tok), jnp.asarray(masked),
                            jnp.asarray(rem), np.int32(n_blocks))
                    self.pool.buffers = buffers
                    self.pool.positions = positions
                    self.pool.live = live
            except Exception as e:
                if is_resource_exhausted(e):
                    self._note_oom(tick, "serve.decode")
                elif not is_transient(e):
                    raise
                attempts += 1
                if attempts > self._retry_limit:
                    for slot, _st in states:
                        if slot in self._sched.active:
                            finished.append(self._quarantine_slot(
                                slot, tick, "decode_failed"))
                    return 0
                self._backoff(attempts)
                continue
            block = {"toks": (blocks, counts), "live": live, "stats": stats,
                     "t_block": steps, "states": states,
                     "pre_pos": pre_pos, "n_blocks": n_blocks,
                     "passes": n_blocks * (self._denoise_steps + 1),
                     "family": f"denoise[T={steps}]"}
            toks_h, _live_h, done = self._fetch_block(block, tick)
            with self._tracer.region("serve.retire", tick=tick) as retire:
                before = len(finished)
                n_tokens = self._consume_blocks(
                    block, done - issue.t0, toks_h, tick, finished)
                retire.count(finished=len(finished) - before)
            return n_tokens
        return 0

    def _consume_blocks(self, block: dict, decode_s: float, fetched,
                        tick: int, finished: list) -> int:
        """Fold one fetched denoising program into the scheduler:
        validate, serve, account, retire. The ``dispatch`` event carries
        the program's counters beside the routing ones: the live slots'
        denoising steps, the tokens they committed and the blocks they
        closed. Returns the tokens served."""
        states, pre_pos = block["states"], block["pre_pos"]
        steps, family = block["t_block"], block["family"]
        if fetched is None:
            for slot, _st in states:
                if slot in self._sched.active:
                    finished.append(self._quarantine_slot(
                        slot, tick, "device_get_failed"))
            return 0
        blocks_h, counts_h = fetched
        blocks_h = np.asarray(blocks_h)
        bad = (blocks_h < 0).any(axis=(1, 2))
        if self._vocab is not None:
            bad |= (blocks_h >= int(self._vocab)).any(axis=(1, 2))
        for slot, _st in states:
            if bad[slot] and slot in self._sched.active:
                finished.append(self._quarantine_slot(
                    slot, tick, "poisoned_token"))
        blk_finished, served, ran = self._sched.consume_blocks(
            blocks_h, block["n_blocks"], tick)
        n_tokens = sum(served.values())
        per_block, length = self._denoise_steps, self._block_len
        # every denoising step of a block reads its slot's clean prefix
        # and the block's own rows (a step that closes the block before
        # it too: that block's rows lie inside them); the last close reads
        # the prefix and the blocks the slot ran
        live_kv = sum(per_block * ((b + 1) * length + pre_pos[slot])
                      for slot, r in ran.items() for b in range(r)) + sum(
            pre_pos[slot] + r * length for slot, r in ran.items())
        self.metrics.record_decode(
            len(states), decode_s, tokens_emitted=n_tokens, block=steps,
            live_kv=live_kv, cache_len=self.cache_len)
        self.metrics.perf.record_dispatch(family, decode_s, tokens=n_tokens)
        decode_ms = round(decode_s * 1e3, 3)
        self.recorder.record(
            "dispatch", tick=tick, family=family, ms=decode_ms,
            tokens=n_tokens, **block.get("routing", {}),
            **{name: int(v) for name, v in counts_h.items()})
        for slot, st in states:
            span = self._spans.get(st.req.id)
            if span is not None:
                span.event("decode", tick=tick, pos=pre_pos[slot],
                           n_active=len(states), block=steps,
                           blocks=ran.get(slot, 0),
                           tokens=served.get(slot, 0), step_ms=decode_ms)
        finished.extend(blk_finished)
        self._note_clean_dispatch(tick)
        return n_tokens

    def _consume_block(self, block: dict, decode_s: float, toks_h,
                       live_h, tick: int, finished: list) -> int:
        """Fold one fetched block of the synchronous loop into the
        scheduler: validate, consume, account, retire. ``decode_s`` runs
        from the block's issue to the end of its fetch. Returns the
        real tokens consumed."""
        states, pre_pos = block["states"], block["pre_pos"]
        n_active, t_block = block["n_active"], block["t_block"]
        family = block["family"]
        if toks_h is None:
            # the block's tokens are unrecoverable on host: every
            # active stream now has a gap — definite failure beats
            # silently resuming with missing tokens
            for slot, _st in states:
                if slot in self._sched.active:
                    finished.append(self._quarantine_slot(
                        slot, tick, "device_get_failed"
                    ))
            return 0

        toks_h = np.asarray(toks_h)
        if toks_h.ndim == 1:
            toks_h = toks_h[:, None]
        if self._faults is not None:
            toks_h = self._faults.poison_block(
                "serve.device_get", toks_h, tick=tick,
                slots=[s for s, _ in states
                       if s in self._sched.active],
                replica=self._replica,
            )
        # token-stream validation (always on — one vectorized pass
        # over an (S, T) int block): greedy tokens are argmax
        # indices in [0, vocab), so anything else is corruption;
        # quarantine the row BEFORE consume() folds it into results
        bad_rows = (toks_h < 0).any(axis=1)
        if self._vocab is not None:
            bad_rows |= (toks_h >= int(self._vocab)).any(axis=1)
        quarantined: set[int] = set()
        if bad_rows.any():
            for slot, _st in states:
                if slot in self._sched.active and bad_rows[slot]:
                    finished.append(self._quarantine_slot(
                        slot, tick, "poisoned_token"
                    ))
                    quarantined.add(slot)

        blk_finished, consumed = self._sched.consume(toks_h, tick)
        n_tokens = sum(consumed.values())
        # live KV rows the block actually attended, per slot: its
        # c consumed micro-steps read frontiers pos0+1 .. pos0+c
        # (an arithmetic series) — vs the c * cache_len rows a
        # dense read would touch, the FLOP-utilization figure
        live_kv = sum(
            c * (pre_pos[slot] + 1) + c * (c - 1) // 2
            for slot, c in consumed.items()
        )
        self.metrics.record_decode(
            n_active, decode_s, tokens_emitted=n_tokens,
            block=t_block, live_kv=live_kv, cache_len=self.cache_len,
        )
        # the dispatch interval spans issue -> the block's ONE
        # existing device_get; analytics adds no sync of its own
        self.metrics.perf.record_dispatch(
            family, decode_s, tokens=n_tokens
        )
        self.recorder.record(
            "dispatch", tick=tick, family=family,
            ms=round(decode_s * 1e3, 3), tokens=n_tokens,
            **block.get("routing", {}),
        )
        if __debug__:
            # the device live mask and the host's retirement
            # bookkeeping must agree slot for slot — the parity
            # contract's cheap runtime cross-check (quarantined
            # slots are exempt: the host retired them while the
            # fetched mask still shows them live)
            for slot, _st in states:
                if slot in quarantined:
                    continue
                assert bool(live_h[slot]) == (
                    slot in self._sched.active
                ), (
                    f"device live mask and host retirement disagree "
                    f"for slot {slot} (block T={t_block})"
                )
        decode_ms = round(decode_s * 1e3, 3)
        for slot, st in states:
            span = self._spans.get(st.req.id)
            if span is not None:
                span.event("decode", tick=tick, pos=pre_pos[slot],
                           n_active=n_active, block=t_block,
                           tokens=consumed.get(slot, 0),
                           step_ms=decode_ms)
        finished.extend(blk_finished)
        self._note_clean_dispatch(tick)
        return n_tokens

    def run(self, max_ticks: int = 100_000) -> dict[int, RequestResult]:
        """Step until queue and slots drain; results keyed by request
        id. ``max_ticks`` bounds runaway loops (a generator that never
        emits EOS still retires at its token budget, so hitting the
        bound means a caller bug — reported as the typed error). The
        error does NOT discard work: completed results ride on it as
        ``err.results``, alongside every still-pending request retired
        with the definite status ``"stalled"`` — and the engine is
        drained afterwards, not wedged."""
        results: dict[int, RequestResult] = {}
        start = self.tick
        # black-box contract: the flight recorder dumps its last N
        # events to the error log automatically when the typed error
        # escapes — the post-mortem for "what was the engine doing"
        with self.recorder.dump_on_friendly_error():
            while self._sched.busy:
                if self.tick - start >= max_ticks:
                    n_queued = self._sched.queue_depth
                    n_active = len(self._sched.active)
                    # abandon any in-flight pipelined block and close
                    # the deferred-free window so the stall's slot
                    # frees land immediately
                    self._inflight = None
                    self.pool.flush_frees(None)
                    for res in self._sched.stall_pending(self.tick):
                        results[res.id] = res
                        self.metrics.record_finish(res)
                        span = self._spans.pop(res.id, None)
                        if span is not None:
                            span.end(res.status, tick=res.finish_tick,
                                     generated=res.generated)
                    err = FriendlyError(
                        f"serve run() exceeded max_ticks ({max_ticks}) "
                        f"with {n_queued} queued and "
                        f"{n_active} active requests; partial results "
                        "(completed + 'stalled') are attached as "
                        "err.results"
                    )
                    err.results = results
                    raise err
                for res in self.step():
                    results[res.id] = res
        return results

    # -- replica control plane (serve/supervisor.py drives these) ----------

    @property
    def queue_full(self) -> bool:
        """True when the next ``submit`` would bounce off admission
        control — the supervisor's router checks this before choosing a
        replica."""
        return self._sched.queue_depth >= self._sched.max_queue

    def cancel(self, request_id: int) -> int | None:
        """Cancel one pending request WITHOUT a terminal result: the
        hedge loser's exit (first-committed-wins — the winning replica
        already committed the stream, this copy's tokens are waste) and
        failover dedup. Queued entries leave the queue; active ones
        free their slot. Returns the emitted-token count discarded, or
        None when the id is unknown/terminal (or the engine is dead —
        its resources are already parked)."""
        if self._dead:
            return None
        emitted = self._sched.cancel(request_id)
        if emitted is None:
            return None
        self._handoffs.pop(request_id, None)
        self.metrics.record_cancel()
        span = self._spans.pop(request_id, None)
        if span is not None:
            span.end("cancelled", tick=self.tick)
        self.recorder.record(
            "cancelled", tick=self.tick, id=request_id, emitted=emitted,
        )
        return emitted

    def steal_all(self) -> list[dict]:
        """Hand off EVERY pending request for migration to another
        replica (zero-loss drain, or stall cleanup): active slots
        preempt — their emitted tokens fold into resume prefixes and
        their slots free — then the queue drains in FIFO order.
        Returns plain payload dicts for :meth:`adopt` on the target
        engine; re-prefilling prompt + prefix there continues each
        stream bit-identically (greedy determinism)."""
        reqs = self._sched.handoff_all() if not self._dead else []
        out = []
        for req in reqs:
            # a stolen request's pending KV payload stays behind: the
            # adopting engine re-prefills from the prompt instead
            self._handoffs.pop(req.id, None)
            out.append({
                "id": req.id,
                "prompt": np.asarray(req.prompt, np.int32),
                "prefix": np.asarray(req.prefix, np.int32),
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                "trace_id": req.trace_id,
            })
            span = self._spans.pop(req.id, None)
            if span is not None:
                span.end("migrated", tick=self.tick,
                         prefix_len=len(req.prefix))
        if out:
            self.recorder.record("handoff", tick=self.tick, n=len(out))
        return out

    def adopt(self, prompt, *, prefix=(), max_new_tokens: int,
              eos_id: int | None = None,
              trace_id: str | None = None) -> int:
        """Admit a request MIGRATED from another replica (drain
        hand-off or failover re-route): ``prefix`` is the tokens the
        source replica already emitted, re-prefilled with the prompt so
        decode resumes exactly where it stopped and accepted tokens are
        never re-emitted. Bypasses ``max_queue`` — the request was
        admitted once already; bouncing it now would turn migration
        into data loss. Returns the new engine-local id."""
        prompt = np.asarray(prompt, np.int32)
        prefix = np.asarray(prefix, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise FriendlyError(
                f"adopt needs a non-empty 1-D prompt, got shape "
                f"{prompt.shape}"
            )
        if len(prefix) >= max_new_tokens:
            raise FriendlyError(
                f"adopted prefix ({len(prefix)} tokens) already meets "
                f"the request budget ({max_new_tokens}); the source "
                "replica should have retired it as completed"
            )
        if int(prompt.size) + max_new_tokens > self.cache_len:
            raise FriendlyError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds this engine's cache_len "
                f"({self.cache_len}); migrate to a replica with equal "
                "cache geometry"
            )
        req = ServeRequest(
            id=self._next_id,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            eos_id=eos_id,
            deadline_tick=None,
            submit_tick=self.tick,
            submit_wall=time.perf_counter(),
            prefix=prefix,
            trace_id=trace_id or f"t{self._next_id}",
        )
        self._sched.queue.append(req)
        self._next_id += 1
        self.metrics.record_submit()
        span = self._tracer.span(
            "request", tick=self.tick, id=req.id, trace=req.trace_id,
            prompt_len=int(prompt.size), max_new_tokens=max_new_tokens,
        )
        span.event("adopted", tick=self.tick, prefix_len=len(prefix))
        self._spans[req.id] = span
        return req.id

    def take_handoffs(self) -> list[dict]:
        """Drain the prefill-role outbox: every KV hand-off payload
        produced since the last call, in hand-off order. Returns []
        on a dead engine — its payloads are unreachable and the fleet
        re-routes those requests from its own ledger (re-prefill,
        bit-identical by greedy determinism)."""
        if self._dead:
            return []
        out, self._outbox = self._outbox, []
        return out

    def adopt_handoff(self, payload: dict) -> int:
        """Admit a cross-replica KV hand-off payload (the dicts
        :meth:`take_handoffs` returns, routed here by
        ``serve/fleet.py``): like :meth:`adopt`, but carrying the
        source replica's prefill output cache plus the first token, so
        admission lands the KV by DIRECT write into the leased slot —
        no prefill program runs here and the continued stream is
        bit-identical to a local prefill. The write travels the
        ``serve.handoff`` fault hook; a payload that cannot land falls
        back to a full local prefill. Returns the new engine-local
        id."""
        if self._block_len:
            raise FriendlyError(
                f"'{self.graph.name}' generates by diffusion over blocks; "
                "a KV hand-off carries one token a time's first token and "
                "linear rows: resubmit the request instead")
        prompt = np.asarray(payload["prompt"], np.int32)
        prefix = np.asarray(payload.get("prefix", ()), np.int32)
        max_new_tokens = int(payload["max_new_tokens"])
        if prompt.ndim != 1 or prompt.size == 0:
            raise FriendlyError(
                f"hand-off payload needs a non-empty 1-D prompt, got "
                f"shape {prompt.shape}"
            )
        if len(prefix) + 1 > max_new_tokens:
            raise FriendlyError(
                f"hand-off prefix ({len(prefix)} tokens) + the first "
                f"token exceed the request budget ({max_new_tokens}); "
                "the prefill replica should have completed it locally"
            )
        if int(payload["length"]) != int(prompt.size) + len(prefix):
            raise FriendlyError(
                f"hand-off payload length ({payload['length']}) does "
                f"not match prompt ({prompt.size}) + prefix "
                f"({len(prefix)}); the payload is torn"
            )
        if int(prompt.size) + max_new_tokens > self.cache_len:
            raise FriendlyError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds this engine's cache_len "
                f"({self.cache_len}); hand off to a replica with equal "
                "cache geometry"
            )
        req = ServeRequest(
            id=self._next_id,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            eos_id=payload.get("eos_id"),
            deadline_tick=None,
            submit_tick=self.tick,
            submit_wall=time.perf_counter(),
            prefix=prefix,
            # the producing replica's trace context survives adoption:
            # the continued stream's span here joins the prefill span
            # there on one id
            trace_id=str(payload.get("trace_id") or f"t{self._next_id}"),
        )
        self._sched.queue.append(req)
        self._handoffs[req.id] = dict(payload)
        self._next_id += 1
        self.metrics.record_submit()
        span = self._tracer.span(
            "request", tick=self.tick, id=req.id, trace=req.trace_id,
            prompt_len=int(prompt.size), max_new_tokens=max_new_tokens,
        )
        span.event("handoff_queued", tick=self.tick,
                   seq_len=int(payload["length"]))
        self._spans[req.id] = span
        return req.id

    def health_counters(self) -> dict:
        """The supervisor's probe surface: liveness/readiness inputs in
        one cheap host-side dict (no device sync) — tick progress,
        queue/slot load, degradation, SLO burn, and the fault/retry
        totals the health model scores."""
        return {
            "tick": self.tick,
            "busy": self.busy,
            "dead": self._dead,
            "role": self.role,
            "queue_depth": self.queue_depth,
            "active": len(self._sched.active),
            "filling": len(self._sched.filling),
            "degraded": self.degraded,
            "slo_burning": (
                bool(self._slo.should_shed)
                if self._slo is not None else False
            ),
            # consecutive burning SLO evaluations — the fleet
            # autoscaler's scale-up signal (serve/fleet.py)
            "slo_burn_ticks": (
                int(self._slo.burn_ticks)
                if self._slo is not None else 0
            ),
            "retries_total": self.metrics.retries_total,
            "quarantined_total": self.metrics.quarantined_total,
            "faults_injected_total": self.metrics.faults_injected_total,
            "tokens_generated": self.metrics.tokens_generated,
        }

    def _park_after_kill(self) -> None:
        """Deterministic device-resource parking for a killed engine:
        every leased slot frees back to the pool — on a paged pool that
        releases the slot's page mappings (refcounts drop; pages return
        to the free lists, or survive only under prefix-cache
        references) — so an in-process supervisor restoring this
        engine's snapshot onto a fresh engine never double-holds
        device state. Host request bookkeeping is kept for post-mortem
        snapshots; the engine refuses further steps."""
        if self._dead:
            return
        self._dead = True
        # undelivered hand-off payloads are unreachable on a dead
        # engine; the fleet re-routes those requests from its ledger
        self._outbox.clear()
        # an in-flight pipelined block dies with the engine: drop the
        # record and close the deferred-free window so every leased
        # slot below releases immediately
        self._inflight = None
        self.pool.flush_frees(None)
        leased = self.pool.leased_slots()
        for slot in leased:
            self.pool.free(slot)
        self.recorder.record(
            "killed", tick=self.tick, parked_slots=len(leased),
        )

    # -- checkpoint / restore ----------------------------------------------

    @property
    def last_snapshot(self) -> dict | None:
        """The most recent COMPLETE periodic checkpoint (see
        ``snapshot_every_ticks`` / :meth:`checkpoint`) — the
        supervisor's recovery point. A checkpoint that failed mid-write
        never lands here."""
        return self._last_snapshot

    def checkpoint(self) -> dict | None:
        """Take one periodic checkpoint through the ``serve.snapshot``
        fault hook. A fault here models a checkpoint failing MID-WRITE:
        the torn snapshot is NOT restorable, so ``last_snapshot`` keeps
        the previous complete one and serving continues (the failure is
        counted + recorded). Returns the new snapshot dict, or None
        when the write failed. An injected ``kill`` at the snapshot
        site is a crash during checkpointing — it parks and re-raises
        like any other kill."""
        try:
            if self._faults is not None:
                self._faults.fire("serve.snapshot", tick=self.tick,
                                  replica=self._replica)
            snap = self.snapshot()
        except EngineKilled:
            self._park_after_kill()
            raise
        except Exception as e:  # noqa: BLE001 — a torn checkpoint must
            # not take serving down; the engine keeps the previous one
            self.metrics.record_snapshot_failure()
            self.recorder.record(
                "snapshot_failed", tick=self.tick, error=str(e),
            )
            return None
        if self._faults is not None:
            # the serve.snapshot silent-corruption drill: the flip
            # lands AFTER the checksum stamp, so the damage is latent
            # until a restore re-hashes the snapshot
            cseed = self._faults.corrupt_spec(
                "serve.snapshot", tick=self.tick, replica=self._replica
            )
            if cseed is not None:
                snap = integrity.flip_bit_json(snap, cseed)
        self._last_snapshot = snap
        self.metrics.record_snapshot()
        self.recorder.record(
            "snapshot", tick=self.tick,
            active=len(snap["active"]), queued=len(snap["queued"]),
        )
        return snap

    def snapshot(self) -> dict:
        """JSON-able checkpoint of ALL host-side request state: every
        queued and active request's prompt, emitted tokens, budget,
        deadline, and the engine tick. Deliberately NO device state —
        restore re-prefills prompt + emitted prefix, and greedy decode
        makes the rebuilt KV frontier (and every post-restore token)
        bit-identical to the uncrashed run, so the checkpoint stays
        tiny and device-layout-agnostic (a single-device snapshot
        restores onto a mesh engine, and vice versa). Call between
        ``step()``s; hand the dict to :meth:`restore` after a crash."""
        if self._block_len:
            raise FriendlyError(
                f"'{self.graph.name}' generates by diffusion over blocks; "
                "its snapshot would have to carry each slot's block in "
                "progress, which it does not yet: no snapshot or restore")
        active = []
        for slot, st in sorted(self._sched.active.items()):
            req = st.req
            active.append({
                "id": req.id,
                "prompt": [int(x) for x in req.prompt],
                "emitted": [int(x) for x in st.out],
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                "deadline_tick": req.deadline_tick,
                "submit_tick": req.submit_tick,
                "trace": req.trace_id,
            })
        queued = []
        # mid-fill requests checkpoint as queued entries with their
        # resume prefix: restore re-prefills from scratch, and since a
        # chunked fill emits no tokens before completion there is no
        # partial-fill state worth carrying — determinism does the rest
        for _slot, fs in sorted(self._sched.filling.items()):
            req = fs.req
            queued.append({
                "id": req.id,
                "prompt": [int(x) for x in req.prompt],
                "emitted": [int(x) for x in req.prefix],
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                "deadline_tick": req.deadline_tick,
                "submit_tick": req.submit_tick,
                "trace": req.trace_id,
            })
        for req in self._sched.queue:
            queued.append({
                "id": req.id,
                "prompt": [int(x) for x in req.prompt],
                "emitted": [int(x) for x in req.prefix],
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                "deadline_tick": req.deadline_tick,
                "submit_tick": req.submit_tick,
                "trace": req.trace_id,
            })
        snap = {
            "version": 1,
            "model": self.graph.name,
            "cache_len": self.cache_len,
            "pad_id": self.pad_id,
            "tick": self.tick,
            "next_id": self._next_id,
            "active": active,
            "queued": queued,
        }
        if self._paged:
            # paging plane (page tables, refcounts, prefix entries):
            # informational — restore() re-prefills and rebuilds the
            # mappings from scratch, but the crash dump stays auditable
            # (refcount totals vs mapped pages)
            snap["paging"] = self.pool.snapshot()
        # canonical-JSON self-checksum: restore() re-hashes and rejects
        # a snapshot whose bytes changed at rest (SnapshotCorruption)
        snap["checksum"] = integrity.json_checksum(snap)
        return snap

    @classmethod
    def restore(cls, snapshot: dict, graph, variables,
                **kwargs) -> "ServeEngine":
        """Rebuild a crashed engine from :meth:`snapshot`: a fresh
        engine (same graph/variables; ``kwargs`` as for the
        constructor) whose queue re-admits every checkpointed request —
        active ones first, carrying their emitted tokens as a resume
        prefix, so re-prefilling prompt + prefix continues each stream
        bit-identically (the crash drill in tests/test_serve_faults.py
        is the proof). Deadlines and the tick counter are absolute and
        survive the rebuild.

        A snapshot that carries a ``checksum`` stamp is re-hashed
        FIRST: a mismatch raises
        :class:`~mmlspark_tpu.core.integrity.SnapshotCorruption` naming
        both hashes before any engine state is rebuilt — the caller
        (the fleet's failover) falls back to a fresh engine + request
        re-admission rather than resuming from lying state."""
        stamp = snapshot.get("checksum")
        if stamp is not None:
            actual = integrity.json_checksum(snapshot)
            if actual != stamp:
                raise SnapshotCorruption(expected=stamp, actual=actual)
        if snapshot.get("version") != 1:
            raise FriendlyError(
                f"unknown serve snapshot version "
                f"{snapshot.get('version')!r} (this build reads "
                "version 1)"
            )
        if snapshot.get("model") != graph.name:
            raise FriendlyError(
                f"snapshot is for model {snapshot.get('model')!r}, "
                f"cannot restore onto {graph.name!r}"
            )
        kwargs.setdefault("cache_len", snapshot["cache_len"])
        kwargs.setdefault("pad_id", snapshot["pad_id"])
        engine = cls(graph, variables, **kwargs)
        engine._sched.tick_count = int(snapshot["tick"])
        engine._next_id = int(snapshot["next_id"])
        now = time.perf_counter()
        # active requests resume FIRST (they were running when the
        # engine died), then the queued ones in their original order —
        # appended directly, bypassing max_queue: these were already
        # admitted once, bouncing them now would turn a crash into
        # data loss
        for entry in list(snapshot["active"]) + list(snapshot["queued"]):
            req = ServeRequest(
                id=int(entry["id"]),
                prompt=np.asarray(entry["prompt"], np.int32),
                max_new_tokens=int(entry["max_new_tokens"]),
                eos_id=entry["eos_id"],
                deadline_tick=entry["deadline_tick"],
                submit_tick=int(entry["submit_tick"]),
                submit_wall=now,
                prefix=np.asarray(entry.get("emitted", ()), np.int32),
                # the failover replay keeps the ORIGINAL trace id, so
                # the re-prefill on the rebuilt engine is causally
                # linked to the pre-crash submit in the merged trace
                trace_id=str(entry.get("trace")
                             or f"t{int(entry['id'])}"),
            )
            engine._sched.queue.append(req)
            engine.metrics.record_submit()
            span = engine._tracer.span(
                "request", tick=engine.tick, id=req.id,
                trace=req.trace_id,
                prompt_len=int(req.prompt.size),
                max_new_tokens=req.max_new_tokens,
            )
            span.event("restored", tick=engine.tick,
                       prefix_len=len(req.prefix))
            engine._spans[req.id] = span
        # the restored engine's initial recovery point IS the snapshot
        # it was built from — a kill before the first periodic refresh
        # still has a complete checkpoint to fail over to
        engine._last_snapshot = snapshot
        return engine
