"""Named-node model graphs with cut-at-node support.

The reference's DNN stage does *graph surgery by node name*: pick an output
node by name or index and re-compose the net up to it
(``CNTKLib.AsComposite``, cntk-model/src/main/scala/CNTKModel.scala:97-108),
and the model-zoo schema publishes ``layerNames`` so ``ImageFeaturizer`` can
cut N layers from the top (image-featurizer/.../ImageFeaturizer.scala:122).
Node-name preservation is load-bearing (SURVEY.md §7 hard parts).

TPU-native re-expression: a model is an ordered sequence of *named blocks*
(flax modules). ``apply(..., output_node=name)`` runs the prefix ending at
that block — XLA then compiles exactly the prefix (dead code past the cut is
never traced), which is strictly cheaper than the reference's runtime
surgery.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Sequence

import jax

from mmlspark_tpu.core.exceptions import FriendlyError

#: conventional name of the final (logits) node — the reference's CNTK models
#: use "z" (notebook 301; CNTKModel.setOutputNodeName("z")).
FINAL_NODE = "z"


@dataclass
class NamedGraph:
    """An ordered, named-block model. ``blocks`` maps name -> flax module;
    order is the dataflow order."""

    name: str
    blocks: list[tuple[str, Any]]
    #: static metadata: expected input shape (per example, no batch dim)
    input_shape: tuple[int, ...] = ()
    #: dtype used for compute (bfloat16 keeps the MXU fed; params stay f32)
    compute_dtype: Any = None
    extra: dict = field(default_factory=dict)

    @property
    def layer_names(self) -> list[str]:
        """Ordered node names (the ModelSchema.layerNames analog,
        downloader/src/main/scala/Schema.scala:54-74)."""
        return [n for n, _ in self.blocks]

    def _check_node(self, node: str | int | None) -> str | None:
        return resolve_node(self.layer_names, node, self.name)

    def init(self, rng, sample):
        """Initialize per-block variables by threading a sample through."""
        variables: dict[str, Any] = {}
        x = sample
        for block_name, mod in self.blocks:
            rng, sub = jax.random.split(rng)
            v = mod.init({"params": sub}, x)
            # sown auxiliary losses are per-call values, not state
            v = {k: c for k, c in v.items() if k != "losses"}
            variables[block_name] = v
            x = mod.apply(v, x)
        return variables

    def apply(
        self,
        variables: dict[str, Any],
        x,
        output_node: str | int | None = None,
        train: bool = False,
        rngs: dict | None = None,
        mask=None,
    ):
        """Forward pass; stops at ``output_node`` when given (headless net).

        In train mode returns ``(out, updated_variables)`` where updated
        variables carry new batch statistics; in eval mode returns ``out``.
        ``mask`` (optional, (B,) 0/1 real-row mask) is forwarded to blocks
        whose ``__call__`` accepts it (e.g. MoE routing excludes padding).
        """
        stop = self._check_node(output_node)
        updated = dict(variables)
        for block_name, mod in self.blocks:
            v = variables[block_name]
            kwargs: dict[str, Any] = {}
            if _accepts_train(mod):
                kwargs["train"] = train
            if mask is not None and _accepts_kwarg(mod, "mask"):
                kwargs["mask"] = mask
            if train:
                has_stats = "batch_stats" in v
                # strip stale sown losses so each call sows fresh values
                v_in = {k: c for k, c in v.items() if k != "losses"}
                mutable = (["batch_stats"] if has_stats else []) + ["losses"]
                x, mutated = mod.apply(
                    v_in,
                    x,
                    mutable=mutable,
                    rngs=rngs,
                    **kwargs,
                )
                if mutated:
                    updated[block_name] = {**v_in, **mutated}
            else:
                x = mod.apply(v, x, **kwargs)
            if block_name == stop:
                break
        return (x, updated) if train else x

    def cut(self, node: str | int) -> "NamedGraph":
        """A new graph truncated after ``node`` (AsComposite equivalent)."""
        stop = self._check_node(node)
        idx = self.layer_names.index(stop)
        return NamedGraph(
            name=f"{self.name}@{stop}",
            blocks=self.blocks[: idx + 1],
            input_shape=self.input_shape,
            compute_dtype=self.compute_dtype,
            extra=dict(self.extra),
        )

    def with_mesh(self, mesh) -> "NamedGraph":
        """This graph with ``mesh`` handed to every block that takes a
        mesh and has none. The sharded engine and trainer call it with
        their own mesh, so that attention runs its Pallas kernels per
        shard — the TPU compiler cannot partition a kernel on its own
        (ops/flash_attention.py ``_kernel_axes``). Variables are
        unaffected: a mesh is a static attribute, not a parameter."""
        blocks = [
            (name, mod.clone(mesh=mesh))
            if getattr(mod, "mesh", mesh) is None else (name, mod)
            for name, mod in self.blocks
        ]
        return replace(self, blocks=blocks)

    def param_count(self, variables) -> int:
        return count_params(variables)


def resolve_node(layer_names: Sequence[str], node: str | int | None,
                 graph_name: str) -> str | None:
    """Resolve an output-node selector (name or index, the CNTKModel
    setOutputNode variants, CNTKModel.scala:166-170) against ordered node
    names; raises FriendlyError for unknown selectors."""
    if node is None:
        return None
    if isinstance(node, int):
        try:
            return layer_names[node]
        except IndexError:
            raise FriendlyError(
                f"output node index {node} out of range for "
                f"{len(layer_names)} nodes"
            )
    if node not in layer_names:
        raise FriendlyError(
            f"no node '{node}' in graph '{graph_name}'; "
            f"nodes: {list(layer_names)}"
        )
    return node


def count_params(variables) -> int:
    """Total leaf element count of a variables pytree."""
    return sum(leaf.size for leaf in jax.tree_util.tree_leaves(variables))


def _accepts_kwarg(mod, name: str) -> bool:
    import inspect

    try:
        return name in inspect.signature(type(mod).__call__).parameters
    except (ValueError, TypeError):  # pragma: no cover
        return False


def _accepts_train(mod) -> bool:
    return _accepts_kwarg(mod, "train")
