"""One answer to "which device", one place for the compile cache, and a
compile refusal that is a crash: the small contracts ``chip_smoke.py``
and the benchmark rest on, so that no run can pass without the chip."""

import jax
import pytest

from mmlspark_tpu.core import env
from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.faults import is_resource_exhausted, is_transient
from mmlspark_tpu.core.perf import DEVICE_PEAKS, device_peak


class _Dev:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


@pytest.mark.parametrize("platform,kind,expected", [
    ("tpu", "TPU v5 lite", True),
    ("tpu", "anything", True),
    ("cpu", "cpu", False),
])
def test_is_tpu_goes_by_platform_only(monkeypatch, platform, kind, expected):
    monkeypatch.setattr(jax, "devices", lambda: [_Dev(platform, kind)])
    assert env.is_tpu() is expected


@pytest.fixture
def cache_config():
    """Restore jax's cache directory after a test that sets it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_placed_from_outside_sets_nothing(
        monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert env.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    import os

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = env.enable_compile_cache()
    second = env.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_device_peak_raises_on_unknown_kind(monkeypatch):
    monkeypatch.delenv("MMLTPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MMLTPU_PEAK_HBM_BYTES_PER_S", raising=False)
    with pytest.raises(FriendlyError, match="Mystery 9000"):
        device_peak(_Dev("tpu", "Mystery 9000"))
    # one override is not enough to stand in for a whole unknown device
    monkeypatch.setenv("MMLTPU_PEAK_FLOPS", "1e12")
    with pytest.raises(FriendlyError, match="Mystery 9000"):
        device_peak(_Dev("tpu", "Mystery 9000"))
    monkeypatch.setenv("MMLTPU_PEAK_HBM_BYTES_PER_S", "1e11")
    peak = device_peak(_Dev("tpu", "Mystery 9000"))
    assert (peak.flops_per_s, peak.hbm_bytes_per_s, peak.source) == (
        1e12, 1e11, "env"
    )


def test_device_peak_cpu_entry_is_explicit_and_labelled(monkeypatch):
    monkeypatch.delenv("MMLTPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MMLTPU_PEAK_HBM_BYTES_PER_S", raising=False)
    peak = device_peak()  # this suite's CPU backend
    assert peak.source == "nominal"
    assert (peak.flops_per_s, peak.hbm_bytes_per_s) == DEVICE_PEAKS["cpu"]
    v5e = device_peak(_Dev("tpu", "TPU v5 lite"))
    assert (v5e.flops_per_s, v5e.hbm_bytes_per_s, v5e.source) == (
        197e12, 819e9, "table"
    )


_COMPILE_REFUSAL = (
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space smem. Used 1.01M of 1.00M smem."
)


def test_compile_refusal_is_not_an_oom():
    refusal = jax.errors.JaxRuntimeError(_COMPILE_REFUSAL)
    assert not is_resource_exhausted(refusal)
    assert not is_transient(refusal)
    assert is_resource_exhausted(jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate 4 GiB"
    ))


@pytest.mark.parametrize("status", [
    "UNAVAILABLE", "DEADLINE_EXCEEDED", "CANCELLED",
])
def test_runtime_error_of_the_installed_class_is_transient(status):
    assert is_transient(jax.errors.JaxRuntimeError(f"{status}: link down"))
    # the status text in any other error type is not retryable
    assert not is_transient(RuntimeError(f"{status}: link down"))


def test_compile_refusal_escapes_the_engine(monkeypatch):
    """A kernel the chip's compiler refuses must raise out of ``run``:
    not retried, not degraded, not turned into ``failed`` requests."""
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.serve.engine import ServeEngine

    graph = build_model("transformer_lm", vocab_size=32, d_model=16,
                        heads=2, depth=1, max_len=32, attn_impl="dense")
    variables = graph.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    engine = ServeEngine(graph, variables, slots=2, cache_len=32,
                         retry_backoff_s=0.0)

    def refuse(*args, **kwargs):
        raise jax.errors.JaxRuntimeError(_COMPILE_REFUSAL)

    # the jitted block itself, under the engine's two counting wrappers
    monkeypatch.setattr(engine._decode._fn, "_fn", refuse)
    engine.submit(np.arange(1, 6), 4)
    with pytest.raises(jax.errors.JaxRuntimeError, match="compile permanent"):
        engine.run()
    m = engine.metrics.to_dict()
    assert m["retries_total"] == 0 and m["failed"] == 0
    assert not engine.degraded
