"""Unit tests for bench.py's emission envelope (no backend needed).

The envelope is the part the driver depends on when everything else goes
wrong, so its rules are pinned directly: headline-value provenance, no
number without the chip unless smoke mode was asked for and labelled,
and scratch persistence.
"""

import importlib
import json
import os
import subprocess
import sys


def _bench(monkeypatch, tmp_path, **env):
    monkeypatch.setenv("MMLTPU_BENCH_SCRATCH", str(tmp_path / "scratch.json"))
    monkeypatch.delenv("MMLTPU_BENCH_CPU_SMOKE", raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    import bench

    return importlib.reload(bench)


def test_headline_null_unless_tpu_provenance(monkeypatch, tmp_path):
    bench = _bench(monkeypatch, tmp_path)
    cpu = bench._final_line(
        {"images_per_sec_per_chip": 700.0,
         "group_backends": {"inference": "cpu"}},
    )
    assert cpu["value"] is None
    assert cpu["images_per_sec_per_chip"] == 700.0  # stays in the body

    tpu = bench._final_line(
        {"images_per_sec_per_chip": 427020.0,
         "group_backends": {"inference": "tpu"}},
    )
    assert tpu["value"] == 427020.0
    assert "images_per_sec_per_chip" not in tpu or tpu["value"] is not None


def test_smoke_mode_is_labelled_and_never_a_headline(monkeypatch, tmp_path):
    bench = _bench(monkeypatch, tmp_path, MMLTPU_BENCH_CPU_SMOKE="1")
    smoke = bench._final_line(
        {"images_per_sec_per_chip": 700.0,
         "group_backends": {"inference": "cpu"}},
    )
    assert smoke["scale"] == "cpu_smoke"
    assert smoke["value"] is None
    # the smoke run proved the bench path: exit 0, not the old "5 is fine"
    assert bench._exit_code(smoke) == 0
    assert bench._exit_code(bench._final_line({})) == 5
    assert bench._exit_code(bench._final_line({}), hung=True) == 7


def test_no_tpu_and_no_smoke_variable_exits_nonzero(tmp_path):
    """The CPU is reached only by asking for it: with no TPU the command
    exits non-zero before any metric and prints no result line — it never
    re-execs onto the CPU on its own."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "MMLTPU_BENCH_CPU_SMOKE"}
    env.update(JAX_PLATFORMS="cpu",
               MMLTPU_BENCH_SCRATCH=str(tmp_path / "scratch.json"))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=repo,
    )
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_one_peak_table(monkeypatch, tmp_path):
    """bench.py reads core/perf.py's table; it keeps none of its own."""
    bench = _bench(monkeypatch, tmp_path)
    from mmlspark_tpu.core.perf import DEVICE_PEAKS

    assert not hasattr(bench, "_PEAK_FLOPS")
    # this suite's CPU hits the nominal entry: no MFU against a made-up peak
    assert bench._peak_flops() is None

    class _V5e:
        device_kind = "TPU v5 lite"

    import jax

    monkeypatch.setattr(jax, "devices", lambda: [_V5e()])
    assert bench._peak_flops() == DEVICE_PEAKS["TPU v5 lite"][0] == 197e12


def test_scratch_merge_roundtrip_and_missing_groups(monkeypatch, tmp_path):
    bench = _bench(monkeypatch, tmp_path)
    merged = bench._scratch_merge({"images_per_sec_per_chip": 1.0, "mfu": 0.1})
    assert bench._group_done(merged, "inference")
    assert not bench._group_done(merged, "flash")
    line = bench._final_line(bench._scratch_load())
    assert set(line["missing_metrics"]) == {
        "stage", "resnet50", "train", "trees", "flash", "flash_long",
        "int8_serving", "feed_synth", "decode", "serve", "serve_paged",
        "serve_int8", "serve_sharded", "serve_faults", "serve_supervisor",
        "serve_disagg", "serve_multimodel", "serve_chunked",
        "train_resilience", "integrity",
    }
    # merge is a real file round-trip: a fresh load sees the update
    with open(os.environ["MMLTPU_BENCH_SCRATCH"], encoding="utf-8") as f:
        assert json.load(f)["mfu"] == 0.1


def test_chained_op_seconds_contract(monkeypatch, tmp_path):
    """The dispatch-cancelling timing harness returns positive
    per-iteration seconds plus a fallback flag, and traces the step per
    chain — not per iteration (the chained iterations live inside one
    lax.scan)."""
    bench = _bench(monkeypatch, tmp_path)
    import jax
    import jax.numpy as jnp

    q = jnp.ones((1, 8, 1, 4), jnp.float32)
    k = v = q
    calls = []

    def step(qq, k, v):
        calls.append(1)
        return qq * 2.0

    secs, fell_back = bench._chained_op_seconds(
        jax, jnp, step, q, k, v, n1=2, n2=4, trials=1
    )
    assert secs > 0 and isinstance(fell_back, bool)
    # per chain (2 chains), never per iteration (n1 + n2 = 6); exact
    # trace counts are JAX-internal, so only the upper bound is pinned
    assert len(calls) < 6


def test_final_stdout_line_is_compact_json(monkeypatch, tmp_path, capsys):
    """The PRINTED terminal line must parse as JSON and stay under the
    compact budget even when the full payload is enormous (the driver's
    bounded tail capture truncates long lines to null) — with the full
    payload written next to bench.py as BENCH_FULL.json."""
    bench = _bench(monkeypatch, tmp_path)
    monkeypatch.setenv(
        "MMLTPU_BENCH_FULL_PATH", str(tmp_path / "BENCH_FULL.json")
    )
    # a deliberately bloated payload: per-group dumps far past the limit
    results = {
        "images_per_sec_per_chip": 427020.0,
        "group_backends": {"inference": "tpu"},
        "group_seconds": {g: 12.3456789 for g in bench._GROUPS},
        "decode": {
            "kv_vs_recompute_speedup": 3.1,
            "decode_blocks": {"speedup_t8_vs_t1": 2.4},
            "blob": ["x" * 64] * 64,
        },
        "serve": {"tokens_per_sec": 512.5, "blob": ["y" * 64] * 64},
    }
    line = bench._final_line(results)
    assert len(json.dumps(line).encode()) > bench._COMPACT_LIMIT_BYTES
    assert bench._emit(line) is True
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)  # valid JSON ...
    assert len(out.encode()) < 1500  # ... under the tail-capture budget
    assert parsed["value"] == 427020.0
    assert parsed["full"] == "BENCH_FULL.json"
    assert "group_seconds" in parsed
    # headline figures surface speedups/throughput without the blobs
    assert any("speedup" in k for k in parsed.get("headlines", {}))
    # the full payload survives intact on disk
    with open(tmp_path / "BENCH_FULL.json", encoding="utf-8") as f:
        full = json.load(f)
    assert full["decode"]["blob"][0] == "x" * 64
    # exactly-once: a second emit is a no-op
    assert bench._emit(line) is False


def test_compact_line_sheds_until_under_budget(monkeypatch, tmp_path):
    """Progressive shedding: even a pathological error string cannot
    push the compact line past the budget."""
    bench = _bench(monkeypatch, tmp_path)
    line = bench._final_line(
        {"group_seconds": {f"g{i}": 1.0 for i in range(40)}},
        error="E" * 5000,
    )
    compact = bench._compact_line(line)
    assert len(json.dumps(compact).encode()) <= bench._COMPACT_LIMIT_BYTES
    assert compact["error"].startswith("E")


def test_vs_baseline_is_own_committed_record(monkeypatch, tmp_path):
    """The reference publishes no numbers, so vs_baseline is the ratio
    against the repo's newest committed BENCH_LOCAL_r*.json headline —
    picked NUMERICALLY (r10 > r4), labeled by source, computed only for
    a TPU-provenance headline, and never able to break emission."""
    import json as _json

    bench = _bench(monkeypatch, tmp_path)
    # controlled record dir: point the module at tmp_path
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    (tmp_path / "BENCH_LOCAL_r4.json").write_text(
        _json.dumps({"value": 1.0e6}))
    (tmp_path / "BENCH_LOCAL_r10.json").write_text(
        _json.dumps({"value": 2.0e6}))
    line = bench._final_line(
        {"images_per_sec_per_chip": 3.0e6,
         "group_backends": {"inference": "tpu"}},
    )
    assert line["vs_baseline"] == 1.5  # vs r10 (numeric sort), not r4
    assert "BENCH_LOCAL_r10" in line["vs_baseline_source"]
    # CPU provenance nulls the headline -> no baseline ratio either
    cpu_line = bench._final_line(
        {"images_per_sec_per_chip": 700.0,
         "group_backends": {"inference": "cpu"}},
    )
    assert cpu_line["value"] is None
    assert cpu_line["vs_baseline"] is None
    # a malformed record must not break emission
    (tmp_path / "BENCH_LOCAL_r11.json").write_text('{"value": "junk"}')
    ok = bench._final_line(
        {"images_per_sec_per_chip": 3.0e6,
         "group_backends": {"inference": "tpu"}},
    )
    assert ok["value"] == 3.0e6  # emission survived
    null_line = bench._final_line({})
    assert null_line["vs_baseline"] is None
    assert "vs_baseline_source" not in null_line
