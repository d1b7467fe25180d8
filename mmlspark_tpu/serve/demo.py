"""Synthetic-traffic serving demo — the ``serve`` subcommand's body and
``bench.py``'s ``serve`` metric group.

Drives a ``ServeEngine`` over a random-init ``transformer_lm`` (tiny by
default; ``model=`` / ``--model`` gives it any shape, GPT-2 small
included, with the model's own default attention — the flash kernels on
a TPU) with a deterministic staggered arrival schedule (a few submits per tick,
prompt lengths drawn from a seeded rng), mirroring ``bench``'s contract:
ONE parseable JSON line out, carrying queue-depth, TTFT, per-token
latency, slot-utilization, and throughput metrics. With
``telemetry_dir`` set (the CLI's ``--telemetry-dir``), the engine's
flight-recorder event timeline lands in ``events.jsonl``, the full
metrics dict in ``metrics.json``, the Perfetto-loadable Chrome trace
in ``trace.json``, and the Prometheus text exposition in
``metrics.prom`` next to them — the schema
``tools/check_metrics_schema.py`` gates (docs/OBSERVABILITY.md).
``trace_out`` (the CLI's ``--trace-out``) writes just the trace to an
explicit path.

Multi-engine runs (``--replicas`` / ``--disagg`` / ``--models``) write
the MERGED :class:`~mmlspark_tpu.core.tracehub.TelemetryHub` bundle
instead: one wall-clock-ordered ``events.jsonl`` across every
replica's recorder, one flow-arrow-stitched ``trace.json``, one
labeled exposition — plus ``supervisor.events.jsonl``, the
control-plane-only timeline in the old format. ``metrics_port`` (the
CLI's ``--metrics-port``) serves the same hub live on 127.0.0.1 while
the demo runs (docs/OBSERVABILITY.md "Distributed tracing").
"""

from __future__ import annotations

import os

import numpy as np


def run_demo(*, slots: int = 4, n_requests: int = 8,
             max_new_tokens: int = 8, arrivals_per_tick: int = 2,
             vocab: int = 64, d_model: int = 32, heads: int = 2,
             depth: int = 2, cache_len: int = 64, max_prompt: int = 16,
             model: str | None = None, seed: int = 0,
             deadline_ticks: int | None = None,
             decode_block: int | None = None,
             mesh: str | None = None,
             telemetry_dir: str | None = None,
             faults: str | None = None,
             slo: str | None = None,
             trace_out: str | None = None,
             paged: bool = False,
             page_size: int | None = None,
             prefix_cache: bool = False,
             replicas: int = 1,
             hedge_ms: float | None = None,
             kv_dtype: str = "bf16",
             quantize_weights: bool = False,
             disagg: bool = False,
             prefill_replicas: int = 1,
             decode_replicas: int = 1,
             autoscale: str | None = None,
             models: str | None = None,
             device_budget: int | None = None,
             prefill_chunk: int | None = None,
             async_host: bool = False,
             metrics_port: int | None = None) -> dict:
    """Run the synthetic-traffic loop; returns the metrics dict the CLI
    prints as its one JSON line. With ``replicas > 1`` the loop drives
    a :class:`~mmlspark_tpu.serve.supervisor.ReplicaSet` instead of a
    single engine (docs/SERVING.md "Replicated serving") and the JSON
    line is the supervisor's ``metrics_dict`` — control-plane totals
    plus one nested dict per replica. With ``disagg`` it drives a
    :class:`~mmlspark_tpu.serve.fleet.DisaggFleet` of dedicated
    prefill/decode replicas (docs/SERVING.md "Disaggregated fleet");
    ``autoscale`` takes the ``"max_decode=4,queue_high=2"``-style
    policy spec. ``model`` is the CLI's shape spec: ':'-separated
    ``key=value`` fields over this function's own shape arguments
    (``vocab``, ``d_model``, ``heads``, ``depth``, ``cache_len``,
    ``max_prompt`` — prompts are drawn from 4..max_prompt tokens)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.core.faults import parse_fault_spec
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.serve.engine import ServeEngine

    if models:
        # --models SPEC -> the multi-model engine (docs/SERVING.md
        # "Multi-model serving"): one deployment per spec entry, LM and
        # batch traffic interleaved under one device budget
        return _run_multimodel_demo(
            models, n_requests=n_requests,
            max_new_tokens=max_new_tokens,
            arrivals_per_tick=arrivals_per_tick, seed=seed,
            device_budget=device_budget,
            injector=parse_fault_spec(faults) if faults else None,
            telemetry_dir=telemetry_dir, trace_out=trace_out,
            prefill_chunk=prefill_chunk, async_host=async_host,
            metrics_port=metrics_port,
        )

    if model:
        shape = _parse_model_spec(model)
        vocab = shape.get("vocab", vocab)
        d_model = shape.get("d_model", d_model)
        heads = shape.get("heads", heads)
        depth = shape.get("depth", depth)
        cache_len = shape.get("cache_len", cache_len)
        max_prompt = shape.get("max_prompt", max_prompt)
    graph = build_model(
        "transformer_lm", vocab_size=vocab, d_model=d_model, heads=heads,
        depth=depth, max_len=cache_len,
    )
    variables = graph.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )
    engine_kwargs = dict(
        slots=slots, cache_len=cache_len,
        max_queue=max(n_requests, 1),
        # "data=4,model=2"-style mesh spec -> the sharded engine
        # (docs/SERVING.md "Sharded serving"); None = single device
        mesh=mesh or None,
        # "ttft_p99_ms=50,error_rate=0.05"-style SLO spec -> rolling-
        # window monitor + load shedding (docs/OBSERVABILITY.md
        # "Declaring SLOs"); None = undeclared
        slo=slo or None,
        retry_backoff_s=0.0,
        # --paged/--page-size/--prefix-cache -> the paged KV-cache pool
        # (docs/SERVING.md "Paged KV cache"); dense slot pool otherwise
        paged=paged, page_size=page_size, prefix_cache=prefix_cache,
        # --kv-dtype int8 / --quantize-weights -> the quantized decode
        # hot path (docs/PERFORMANCE.md "Quantized decode")
        kv_dtype=kv_dtype, quantize_weights=quantize_weights,
        # --prefill-chunk N / --async-host -> chunked prefill + the
        # pipelined host loop (docs/PERFORMANCE.md "Chunked prefill &
        # async host loop"); threads through every engine mode —
        # single, --replicas, --disagg — via these shared kwargs
        prefill_chunk=prefill_chunk, async_host=async_host,
        # None = the engine's fused decode-block default (32)
        **({} if decode_block is None else {"decode_block": decode_block}),
    )
    # "seed=7,transient=0.05,oom=0.02"-style fault spec -> seeded
    # chaos injection (docs/OBSERVABILITY.md "Fault injection");
    # None = no injector, hooks cost one attribute check
    injector = parse_fault_spec(faults) if faults else None
    if disagg:
        from mmlspark_tpu.serve.fleet import DisaggFleet

        target = DisaggFleet(
            graph, variables, prefill_replicas=prefill_replicas,
            decode_replicas=decode_replicas, autoscale=autoscale or None,
            faults=injector, **engine_kwargs,
        )
    elif replicas > 1:
        from mmlspark_tpu.serve.supervisor import ReplicaSet

        target = ReplicaSet(
            graph, variables, replicas=replicas, hedge_ms=hedge_ms,
            faults=injector, **engine_kwargs,
        )
    else:
        target = ServeEngine(graph, variables, faults=injector,
                             **engine_kwargs)

    # multi-engine modes get a TelemetryHub: the merge point that
    # stitches every replica's recorder/registry into ONE bundle and
    # backs the live /metrics endpoint (docs/OBSERVABILITY.md
    # "Distributed tracing"). Single-engine mode only builds one when
    # the endpoint is requested — its on-disk bundle stays the
    # schema-pinned single-recorder format.
    hub = None
    if disagg or replicas > 1 or metrics_port is not None:
        from mmlspark_tpu.core.tracehub import TelemetryHub

        hub = TelemetryHub()
        if disagg:
            hub.attach_fleet(target)
        elif replicas > 1:
            hub.attach_replicaset(target)
        else:
            hub.attach_engine(target)
    server = None
    if metrics_port is not None:
        from mmlspark_tpu.core.tracehub import MetricsServer

        server = MetricsServer(hub, port=metrics_port)

    rng = np.random.default_rng(seed)
    lo, hi = 4, max(5, min(max_prompt, cache_len - max_new_tokens))
    lengths = rng.integers(lo, hi + 1, size=n_requests)
    prompts = [rng.integers(0, vocab, size=int(p)) for p in lengths]

    try:
        submitted = 0
        results = {}
        while submitted < n_requests or target.busy:
            for _ in range(arrivals_per_tick):
                if submitted < n_requests:
                    target.submit(
                        prompts[submitted], max_new_tokens,
                        deadline_ticks=deadline_ticks,
                    )
                    submitted += 1
            for res in target.step():
                results[res.id] = res
    finally:
        if server is not None:
            server.close()

    if disagg or replicas > 1:
        out = target.metrics_dict()
        recorder = target.recorder
        registry = target.registry
    else:
        out = target.metrics.to_dict()
        out.update(
            decode_compiles=target.decode_compile_count,
            prefill_compiles=target.prefill_compile_count,
            prefill_bucket_count=target.num_prefill_buckets,
        )
        recorder = target.recorder
        registry = target.metrics.registry
    out.update(
        n_requests=n_requests,
        arrivals_per_tick=arrivals_per_tick,
        max_new_tokens=max_new_tokens,
        cache_len=cache_len,
        model_config={"vocab": vocab, "d_model": d_model, "heads": heads,
                      "depth": depth},
    )
    if server is not None:
        out["metrics_port"] = server.port
    if telemetry_dir:
        os.makedirs(telemetry_dir, exist_ok=True)
        if hub is not None and (disagg or replicas > 1):
            # the MERGED bundle: every replica's events/metrics plus
            # the control plane's, stitched by the hub — the fix for
            # the old behavior of dumping ONLY the supervisor's
            # recorder and silently dropping per-engine telemetry.
            # The control-plane-only timeline stays available as
            # supervisor.events.jsonl for consumers of the old format.
            hub.write_bundle(telemetry_dir, metrics=out)
            recorder.dump(
                os.path.join(telemetry_dir, "supervisor.events.jsonl")
            )
        else:
            from mmlspark_tpu.core.perf import export_chrome_trace
            from mmlspark_tpu.core.telemetry import (
                atomic_write_json, atomic_write_text,
            )

            # single-engine bundle: ONE recorder/registry, file formats
            # pinned by tools/check_metrics_schema.py — writes go
            # through the atomic helpers so a kill mid-dump can't
            # leave a torn file
            recorder.dump(os.path.join(telemetry_dir, "events.jsonl"))
            atomic_write_json(
                os.path.join(telemetry_dir, "metrics.json"), out,
                indent=1, default=str,
            )
            export_chrome_trace(
                recorder,
                path=os.path.join(telemetry_dir, "trace.json"),
                extra_meta={"model": graph.name},
            )
            atomic_write_text(
                os.path.join(telemetry_dir, "metrics.prom"),
                registry.to_prometheus(),
            )
    if trace_out:
        if hub is not None and (disagg or replicas > 1):
            hub.export_trace(path=trace_out,
                             extra_meta={"model": graph.name})
        else:
            from mmlspark_tpu.core.perf import export_chrome_trace

            export_chrome_trace(recorder, path=trace_out,
                                extra_meta={"model": graph.name})
    return out


_MODEL_KEYS = ("vocab", "d_model", "heads", "depth", "cache_len",
               "max_prompt")


def _parse_model_spec(spec: str) -> dict:
    """``"d_model=768:heads=12"`` -> ``{"d_model": 768, "heads": 12}``:
    the ``--models`` field grammar over :func:`run_demo`'s shape
    arguments; anything else is a typed error naming the vocabulary."""
    from mmlspark_tpu.core.exceptions import FriendlyError

    out = {}
    for field in filter(None, (f.strip() for f in spec.split(":"))):
        key, eq, value = field.partition("=")
        if not eq or key not in _MODEL_KEYS or not value.isdigit():
            raise FriendlyError(
                f"bad --model field {field!r}: expected key=<int> with "
                f"key one of {', '.join(_MODEL_KEYS)}"
            )
        out[key] = int(value)
    return out


def _run_multimodel_demo(spec: str, *, n_requests: int,
                         max_new_tokens: int, arrivals_per_tick: int,
                         seed: int, device_budget: int | None,
                         injector, telemetry_dir: str | None,
                         trace_out: str | None,
                         prefill_chunk: int | None = None,
                         async_host: bool = False,
                         metrics_port: int | None = None) -> dict:
    """The ``--models`` body: spec -> MultiModelEngine, then a
    deterministic interleaved arrival schedule — ``n_requests`` per
    deployment, token prompts for LM deployments and float feature
    examples for batch deployments, round-robin across models so every
    queue stays contended. One JSON line out: the engine's
    ``metrics_dict`` (per-model nested dicts + the shared registry's
    ``model{name}.serve.*`` flat keys)."""
    from mmlspark_tpu.serve.engine import ServeEngine
    from mmlspark_tpu.serve.multimodel import engine_from_spec

    lm_kwargs = {}
    if prefill_chunk is not None:
        lm_kwargs["prefill_chunk"] = prefill_chunk
    if async_host:
        lm_kwargs["async_host"] = True
    engine = engine_from_spec(
        spec, device_budget=device_budget, faults=injector, seed=seed,
        lm_kwargs=lm_kwargs,
    )
    rng = np.random.default_rng(seed)
    streams: dict[str, list] = {}
    for name in engine.models:
        dep = engine.deployment(name)
        reqs = []
        for _ in range(n_requests):
            if isinstance(dep, ServeEngine):
                vocab = int(dep.graph.extra.get("vocab_size", 16))
                hi = max(5, min(16, dep.cache_len - max_new_tokens))
                plen = int(rng.integers(4, hi + 1))
                reqs.append((rng.integers(0, vocab, size=plen),
                             max_new_tokens))
            else:
                shape = tuple(dep.graph.input_shape)
                reqs.append(
                    (rng.normal(size=shape).astype(np.float32), None)
                )
        streams[name] = reqs
    arrivals = [
        (name, *streams[name][i])
        for i in range(n_requests) for name in engine.models
    ]
    # the hub gives --models telemetry per-deployment {model="name"}
    # labels (instead of model{name}. prefixes) and the live endpoint
    from mmlspark_tpu.core.tracehub import TelemetryHub

    hub = TelemetryHub()
    hub.attach_multimodel(engine)
    server = None
    if metrics_port is not None:
        from mmlspark_tpu.core.tracehub import MetricsServer

        server = MetricsServer(hub, port=metrics_port)
    try:
        submitted = 0
        results = {}
        while submitted < len(arrivals) or engine.busy:
            for _ in range(arrivals_per_tick):
                if submitted < len(arrivals):
                    name, x, budget = arrivals[submitted]
                    if budget is None:
                        engine.submit(x, model=name)
                    else:
                        engine.submit(x, model=name,
                                      max_new_tokens=budget)
                    submitted += 1
            for res in engine.step():
                results[res.id] = res
    finally:
        if server is not None:
            server.close()
    out = engine.metrics_dict()
    out.update(
        n_requests=n_requests,
        arrivals_per_tick=arrivals_per_tick,
        max_new_tokens=max_new_tokens,
        models_spec=spec,
    )
    if server is not None:
        out["metrics_port"] = server.port
    if telemetry_dir:
        hub.write_bundle(telemetry_dir, metrics=out)
    if trace_out:
        hub.export_trace(path=trace_out,
                         extra_meta={"model": "multimodel"})
    return out
