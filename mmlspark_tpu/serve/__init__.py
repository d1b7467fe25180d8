"""Continuous-batching serving engine over a slot-based KV-cache pool.

The subsystem that turns ``models/generate.py``'s per-call static-shape
decode into a multi-tenant engine (docs/SERVING.md): a preallocated
K/V pool of ``slots x cache_len`` rows (:mod:`cache_pool`; head-major
on one device in bf16, linear ``(slots, cache_len, hk, d)`` otherwise), a
tick-based continuous-batching scheduler (:mod:`scheduler`), the public
``ServeEngine.submit/step/run`` API with admission control and
per-request deadlines (:mod:`engine`), serving observability as
``MetricData`` records (:mod:`metrics`), and a synthetic-traffic demo
(:mod:`demo`, the ``python -m mmlspark_tpu serve`` body).

The engine is fault-tolerant (docs/SERVING.md "Failure semantics"):
transient dispatch errors retry, ``RESOURCE_EXHAUSTED`` degrades
gracefully, poisoned/undispatachable requests quarantine with terminal
status ``"failed"`` instead of killing ``run()``, and
``ServeEngine.snapshot()``/``restore()`` checkpoint host-side request
state for crash recovery. :class:`~mmlspark_tpu.core.faults.FaultInjector`
(re-exported here) is the deterministic harness that proves all of it.

For replicated serving, :class:`~mmlspark_tpu.serve.supervisor.ReplicaSet`
(docs/SERVING.md "Replicated serving") puts N engines behind one
``submit()/run()`` facade with health probes, snapshot-based failover,
hedged routing, and zero-loss drain.

For DISAGGREGATED serving, :class:`~mmlspark_tpu.serve.fleet.DisaggFleet`
(docs/SERVING.md "Disaggregated fleet") splits the replicas into
dedicated prefill and decode roles behind the same facade: prefill
replicas ship each request's KV + first token to decode replicas over
a cross-replica hand-off plane (the ``serve.handoff`` fault site), a
fleet-wide prefix index makes any replica's completed prefill every
replica's cache hit, and an :class:`~mmlspark_tpu.serve.fleet.AutoscalePolicy`
grows/shrinks each role elastically from a parked device budget.

For MULTI-MODEL serving, :class:`~mmlspark_tpu.serve.multimodel.
MultiModelEngine` (docs/SERVING.md "Multi-model serving") hosts several
named deployments — stateful LM-decode engines next to stateless
power-of-two-bucketed batch deployments over any non-causal
``build_model`` graph (ONNX-imported included) — behind one
``submit(model=...)`` facade with per-model admission/SLOs/telemetry
namespaces, a round-robin device budget, and the ``serve.batch`` fault
site covering stateless dispatches.
"""

from mmlspark_tpu.core.faults import (  # noqa: F401
    Fault,
    FaultInjector,
    parse_fault_spec,
)
from mmlspark_tpu.core.perf import (  # noqa: F401
    PerfAnalytics,
    SloMonitor,
    SloTargets,
    export_chrome_trace,
    parse_slo_spec,
)
from mmlspark_tpu.serve.cache_pool import SlotCachePool  # noqa: F401
from mmlspark_tpu.serve.engine import ServeEngine  # noqa: F401
from mmlspark_tpu.serve.fleet import (  # noqa: F401
    AutoscalePolicy,
    DisaggFleet,
    parse_autoscale_spec,
)
from mmlspark_tpu.serve.metrics import ServeMetrics  # noqa: F401
from mmlspark_tpu.serve.multimodel import (  # noqa: F401
    BatchDeployment,
    BatchResult,
    MultiModelEngine,
    engine_from_spec,
    parse_models_spec,
)
from mmlspark_tpu.serve.scheduler import (  # noqa: F401
    ContinuousBatchScheduler,
    RequestResult,
    ServeRequest,
)
from mmlspark_tpu.serve.supervisor import ReplicaSet  # noqa: F401
