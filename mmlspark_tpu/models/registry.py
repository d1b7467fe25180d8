"""Model-architecture registry: name -> NamedGraph builder.

Serialized ``TPUModel`` stages store ``(model_name, model_config)`` and
rebuild the graph here at load time — the role the serialized CNTK protobuf
played for the reference (SerializableFunction.scala:13-38), but with
architecture-as-code instead of opaque graph bytes.
"""

from __future__ import annotations

from typing import Any, Callable

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.models.graph import NamedGraph

_BUILDERS: dict[str, Callable[..., NamedGraph]] = {}


def register_model(name: str):
    def deco(fn: Callable[..., NamedGraph]):
        _BUILDERS[name] = fn
        return fn

    return deco


def build_model(name: str, **config: Any) -> NamedGraph:
    _ensure_loaded()
    if name not in _BUILDERS:
        import difflib

        hint = difflib.get_close_matches(name, sorted(_BUILDERS), n=1)
        suggest = f"; did you mean '{hint[0]}'?" if hint else ""
        raise FriendlyError(
            f"unknown model '{name}'; registered: "
            f"{sorted(_BUILDERS)}{suggest} (foreign graphs load via "
            "name 'onnx' with path=<file.onnx>)"
        )
    return _BUILDERS[name](**config)


def registered_models() -> list[str]:
    _ensure_loaded()
    return sorted(_BUILDERS)


def _ensure_loaded() -> None:
    # builder modules self-register on import
    import mmlspark_tpu.models.bilstm  # noqa: F401
    import mmlspark_tpu.models.hybrid  # noqa: F401
    import mmlspark_tpu.models.mlp  # noqa: F401
    import mmlspark_tpu.models.moe  # noqa: F401
    import mmlspark_tpu.models.onnx_import  # noqa: F401
    import mmlspark_tpu.models.pipelined  # noqa: F401
    import mmlspark_tpu.models.resnet  # noqa: F401
    import mmlspark_tpu.models.transformer  # noqa: F401
