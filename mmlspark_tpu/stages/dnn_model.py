"""TPUModel — compiled-DNN inference as a pipeline stage.

The CNTKModel re-expression (reference:
cntk-model/src/main/scala/CNTKModel.scala). Feature-for-feature:

| reference                                   | here                          |
|---------------------------------------------|-------------------------------|
| model bytes broadcast to executors (:248)   | weights live in device HBM    |
| per-partition clone + minibatch loop (:51-88)| fixed-shape batch iterator +  |
|                                             | one jit-compiled forward      |
| output-node surgery via AsComposite (:97-108)| ``output_node`` name/index on |
|                                             | the NamedGraph prefix         |
| input coercion UDFs Double/Vector->Float    | stack + astype float32/int32  |
|   (:228-245)                                |                               |
| ``setModelLocation`` file load (:151-154)   | ``set_model_location``        |
| miniBatchSize param (default 10, :205)      | ``batch_size`` (TPU-sized     |
|                                             | default 128)                  |

Parallelism: the reference is embarrassingly data-parallel over Spark
executors; here batches are sharded over the mesh's ``data`` axis with XLA
doing the placement (SURVEY.md §2.5 row 1).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.params import HasInputCol, HasOutputCol, Param, positive
from mmlspark_tpu.core.schema import SCORES_COLUMN
from mmlspark_tpu.core.stage import Model
from mmlspark_tpu.data.dataset import Dataset
from mmlspark_tpu.data.feed import MASK_COL, batch_iterator, stack_column
from mmlspark_tpu.models.graph import NamedGraph
from mmlspark_tpu.models.registry import build_model


class TPUModel(Model, HasInputCol, HasOutputCol):
    """Batched DNN inference on TPU; the NN is just another stage."""

    model_name = Param("registered architecture name", ptype=str, required=True)
    model_config = Param("architecture config kwargs", default=dict, ptype=dict)
    weights = Param("model variables pytree (per-block)")
    batch_size = Param(
        "rows per compiled forward step (minibatch)", 128, ptype=int,
        validator=positive,
    )
    output_node = Param(
        "output node name or index; None = full net (CNTK 'z' convention)"
    )
    data_parallel = Param(
        "shard batches over all visible devices (mesh data axis)", True,
        ptype=bool,
    )
    feed_depth = Param(
        "max in-flight batches in the async host->HBM pipeline (batch "
        "i+1's copy overlaps batch i's compute; higher = more overlap, "
        "more HBM held by pending outputs)", 2, ptype=int,
        validator=positive,
    )
    weight_quant = Param(
        "device-resident weight precision: 'none' keeps the trained "
        "dtype; 'int8' stores kernels as per-channel symmetric int8 in "
        "HBM and dequantizes to bf16 inside the jitted forward "
        "(weight-only W8 — a bandwidth lever; see ops/quantize.py)",
        "none", domain=("none", "int8"),
    )
    feed_dtype = Param(
        "host->HBM transfer dtype for FLOAT inputs: 'float32' ships "
        "rows as-is; 'bfloat16' casts on the host before device_put — "
        "half the host->device transfer bytes. The conv stack computes "
        "in bf16 either way, so only the input quantization step moves. "
        "Integer (token) inputs are unaffected.",
        "float32", domain=("float32", "bfloat16"),
    )

    def __init__(self, **kwargs: Any):
        kwargs.setdefault("output_col", SCORES_COLUMN)
        super().__init__(**kwargs)
        self._graph: NamedGraph | None = None
        self._jitted: dict = {}

    # -- construction helpers ----------------------------------------------

    @classmethod
    def from_graph(
        cls, graph: NamedGraph, variables, model_name: str, **kwargs: Any
    ) -> "TPUModel":
        m = cls(model_name=model_name, **kwargs)
        m.set(weights=variables)
        m._graph = graph
        return m

    def set_model_location(self, path: str) -> "TPUModel":
        """Load weights from a saved stage directory (reference
        ``setModelLocation`` reading model bytes off the filesystem,
        CNTKModel.scala:151-154)."""
        from mmlspark_tpu.core.stage import PipelineStage

        loaded = PipelineStage.load(path)
        if not isinstance(loaded, TPUModel):
            raise FriendlyError(f"{path} does not hold a TPUModel")
        self.set(
            model_name=loaded.model_name,
            model_config=loaded.model_config,
            weights=loaded.weights,
        )
        self._graph = None
        self._jitted = {}
        return self

    def graph(self) -> NamedGraph:
        if self._graph is None:
            self._graph = build_model(self.model_name, **(self.model_config or {}))
        return self._graph

    @property
    def layer_names(self) -> list[str]:
        return self.graph().layer_names

    # -- execution ----------------------------------------------------------

    def _forward(self):
        """The jit-compiled forward for the current output node; compiled
        once per (output_node) and reused across batches (the analog of the
        per-executor model clone being reused per partition)."""
        import jax

        key = (self.output_node, self.weight_quant)
        if key not in self._jitted:
            graph = self.graph()
            node = self.output_node
            quant = self.weight_quant

            def fwd(variables, x):
                if quant == "int8":
                    from mmlspark_tpu.ops.quantize import dequantize_weights

                    # inside jit: XLA fuses the int8->bf16 convert into
                    # the consuming conv/matmul; HBM holds int8
                    variables = dequantize_weights(variables)
                return graph.apply(variables, x, output_node=node)

            # no donation: the output never has the batch's shape, so the
            # chip's compiler cannot alias them ("donated buffers were
            # not usable" on every compile)
            self._jitted[key] = jax.jit(fwd)
        return self._jitted[key]

    def _device_weights(self):
        """Weights live in HBM across transform calls (the analog of the
        broadcast model staying resident per executor, CNTKModel.scala:248);
        re-put only when the weights param is replaced. Validity is an
        identity check against a STRONG reference to the host pytree —
        never a raw id(), which CPython reuses once the old object is
        collected (and the strong ref costs nothing: self.weights holds
        the same object)."""
        import jax

        src_key = (self.weights, self.weight_quant)
        cached = getattr(self, "_dev_weights_src", (None, None))
        if cached[0] is not src_key[0] or cached[1] != src_key[1]:
            host = self.weights
            if self.weight_quant == "int8":
                from mmlspark_tpu.core.logging_utils import get_logger
                from mmlspark_tpu.ops.quantize import quantize_weights

                # measured honesty (docs/PERFORMANCE.md): at
                # compute-bound batch sizes W8 REGRESSED on v5e (MFU
                # 0.18 vs 0.39 bf16, r4 sweep); it is a bandwidth lever
                # for weight-bound serving shapes only
                get_logger(__name__).warning(
                    "weight_quant='int8' is a weight-bandwidth lever: "
                    "measured SLOWER than bf16 at compute-bound batch "
                    "sizes on v5e (see docs/PERFORMANCE.md); use for "
                    "latency-bound small-batch serving or HBM relief"
                )
                host = quantize_weights(host)
            self._dev_weights = jax.device_put(host)
            self._dev_weights_src = src_key
        return self._dev_weights

    def _sharding(self):
        import jax

        if not self.data_parallel or jax.device_count() == 1:
            return None
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = np.array(jax.devices())
        mesh = Mesh(devs, ("data",))
        return NamedSharding(mesh, P("data"))

    def _coerce_input(self, dataset: Dataset) -> Dataset:
        """Input coercion (reference CNTKModel.scala:228-245): whatever the
        column holds — lists, object vectors, int sequences — becomes one
        typed ndarray column."""
        col = self.input_col
        arr = stack_column(dataset, col)
        if arr.dtype == object:
            raise FriendlyError(
                f"input column '{col}' is ragged; bucket or pad first", self.uid
            )
        if np.issubdtype(arr.dtype, np.integer):
            arr = arr.astype(np.int32)
        elif arr.dtype != np.float32:
            arr = arr.astype(np.float32)
        return dataset.with_column(col, arr, dataset.meta_of(col))

    def _transform(self, dataset: Dataset) -> Dataset:
        import jax

        if self.weights is None:
            raise FriendlyError("no weights set; fit or set_model_location first",
                                self.uid)
        ds = self._coerce_input(dataset)
        fwd = self._forward()
        sharding = self._sharding()
        n_dev = len(sharding.mesh.devices.ravel()) if sharding is not None else 1
        batch = self.batch_size
        if batch % n_dev:
            batch += n_dev - batch % n_dev  # divisible by mesh for even shards
        weights = self._device_weights()
        # Async pipeline (replaces the reference's strictly serial
        # per-minibatch JNI copy->evaluate->copy loop, CNTKModel.scala:51-88):
        # device_put and the jit dispatch are non-blocking, so batch i+1's
        # host->HBM copy overlaps batch i's compute; results are fetched a
        # few steps behind, bounding device-resident outputs.
        max_inflight = self.feed_depth
        inflight: list = []
        outs = []

        def drain(limit: int):
            while len(inflight) > limit:
                y0, m0 = inflight.pop(0)
                outs.append(np.asarray(y0)[m0])

        feed_cast = None
        if self.feed_dtype == "bfloat16":
            import jax.numpy as jnp

            feed_cast = jnp.bfloat16  # the ml_dtypes scalar type
        for b in batch_iterator(ds, [self.input_col], batch):
            x = b[self.input_col]
            if feed_cast is not None and np.issubdtype(x.dtype, np.floating):
                x = x.astype(feed_cast)
            x = jax.device_put(x, sharding)  # sharding=None -> default dev
            y = fwd(weights, x)
            inflight.append((y, b[MASK_COL]))
            drain(max_inflight)
        drain(0)
        result = (
            np.concatenate(outs, axis=0)
            if outs
            else np.zeros((0,), dtype=np.float32)
        )
        return dataset.with_column(self.output_col, result)
