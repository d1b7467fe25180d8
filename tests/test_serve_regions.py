"""The serving engine's compile counts and its regions (mmlspark_tpu.serve).

The contract under test (docs/SERVING.md, docs/OBSERVABILITY.md): the
fused decode step compiles once a block size and the prefill once a
bucket whatever lengths arrive, and every admission and every tick
leave their regions in the recorder.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from mmlspark_tpu.serve import ServeEngine
from mmlspark_tpu.testing.compile_guard import (
    compile_guard,
    serve_compile_guard,
)
from tests.serve_helpers import init_lm, ref_tokens, tiny_lm, trained_lm


# -- compile-count invariants (bucketed prefill + fused decode) -------------


def test_mixed_length_soak_pins_compile_counts():
    """Soak with mixed-length joiners: every distinct prompt length in
    [1, 12] flows through 2 slots. The fused decode step must compile
    exactly once and bucketed prefill at most once per power-of-two
    bucket — NOT once per distinct length — while every request still
    matches single-request ``generate()`` byte for byte."""
    m, v, ids = trained_lm()
    lengths = [4, 1, 12, 7, 8, 3, 10, 2, 5, 9]  # raggedy on purpose
    prompts = [np.asarray(ids[0, :n]) for n in lengths]
    engine = ServeEngine(m, v, slots=2, cache_len=32, max_queue=16)
    assert engine.num_prefill_buckets == 3  # 8, 16, 32
    rids = []
    with serve_compile_guard(engine, min_decode=1, min_prefill=1):
        results = {}
        for i, p in enumerate(prompts):  # two joiners per tick
            rids.append(engine.submit(p, max_new_tokens=4))
            if i % 2:
                results.update({r.id: r for r in engine.step()})
        results.update(engine.run())
    for rid, p in zip(rids, prompts):
        want = ref_tokens(m, v, p, 4)
        np.testing.assert_array_equal(np.asarray(results[rid].tokens), want)
    # the 10 distinct lengths landed in at most 2 buckets (8 and 16):
    # far fewer programs than the per-length prefill would have traced
    assert engine.prefill_compile_count <= 2
    buckets = engine.metrics.prefill_buckets
    assert set(buckets) <= {"8", "16"}
    assert sum(buckets.values()) == len(prompts)
    # length-aware decode touched strictly less KV than a dense read
    d = engine.metrics.to_dict()
    assert 0.0 < d["decode_flop_utilization"] < 1.0
    assert d["decode_live_kv_tokens"] < d["decode_dense_kv_tokens"]


# -- regions of the admit path and the tick ----------------------------------

ADMISSION = ("serve.admit_one", "serve.prefill", "serve.prefill_dispatch",
             "serve.pool_write", "serve.first_token", "serve.handoff")
TICK = ("serve.tick", "serve.admit", "serve.decode", "serve.fetch",
        "serve.retire")


@pytest.mark.parametrize("options", [
    {},
    {"async_host": True},
    {"prefill_chunk": 8},
    {"paged": True, "page_size": 8},
], ids=["sync", "async_host", "prefill_chunk", "paged"])
def test_every_admission_and_every_tick_leave_their_regions(options):
    """Whichever option is on, an admitted request leaves exactly one
    ``serve.admit_one`` with one ``serve.pool_write`` and one
    ``serve.first_token`` inside it, at most 8 region events an admission
    and 6 a tick: counts, so nothing here can flake on a timing."""
    m = tiny_lm()
    v = init_lm(m)
    engine = ServeEngine(m, v, slots=2, cache_len=32, **options)
    rng = np.random.default_rng(0)
    rids = [engine.submit(rng.integers(0, 8, size=n).astype(np.int32),
                          max_new_tokens=6) for n in (4, 6, 7, 12, 5)]
    results = engine.run()
    assert all(results[r].status == "completed" for r in rids)

    events = engine.recorder.events()
    regions = [e for e in events if e["name"].startswith("serve.")]
    assert {e["name"] for e in regions} <= set(ADMISSION + TICK)
    assert all(e["attrs"]["ms"] >= 0 and e["attrs"]["t0"] > 0
               for e in regions)
    by_request: dict = {}
    for e in regions:
        if e["name"] in ADMISSION:
            by_request.setdefault(e["attrs"]["request"], []).append(e)
    assert sorted(by_request) == sorted(rids)
    chunks = 0
    for rid, evs in by_request.items():
        names = [e["name"] for e in evs]
        assert names.count("serve.admit_one") == 1, names
        assert names.count("serve.pool_write") == 1
        assert names.count("serve.first_token") == 1
        assert len(evs) <= 8, names
        parent = {e["name"]: e["attrs"]["parent"] for e in evs}
        # the write and the wait lie in the prefill that made them, and
        # that in the request's one admission
        assert parent["serve.pool_write"] == "serve.prefill"
        assert parent["serve.first_token"] == "serve.prefill"
        assert parent["serve.admit_one"] == "serve.admit"
        one = next(e for e in evs if e["name"] == "serve.admit_one")
        inside = [e for e in evs if e is not one
                  and one["attrs"]["t0"] <= e["attrs"]["t0"]
                  and e["t"] <= one["t"]]
        assert {"serve.pool_write", "serve.first_token"} <= {
            e["name"] for e in inside}
        assert one["attrs"]["prompt_len"] in (4, 5, 6, 7, 12)
        assert one["attrs"]["slot"] in (0, 1)
        chunks += names.count("serve.prefill") - 1
    # a chunked fill adds a prefill and its dispatch for every chunk
    # before the last: only the 12-token prompt has one
    assert chunks == (1 if "prefill_chunk" in options else 0)
    per_tick: dict = {}
    for e in regions:
        if e["name"] in TICK:
            per_tick[e["tick"]] = per_tick.get(e["tick"], 0) + 1
    assert len(per_tick) == engine.tick and max(per_tick.values()) <= 6
    # one fetch and one consume for every dispatched block
    count = {n: sum(e["name"] == n for e in regions) for n in TICK}
    assert count["serve.decode"] == count["serve.fetch"] > 0
    assert count["serve.tick"] == count["serve.admit"] == engine.tick
    finished = sum(e["attrs"]["finished"] for e in regions
                   if e["name"] == "serve.retire")
    assert finished == len(rids)
    # what the pool counts: the dense pool's one jitted write; in the
    # paged pool a slice and a scatter for each K and each V array (the
    # prefill cache has the pool's dtype), positions and live, the page
    # and offset vectors, a head index a block and, when the tables
    # changed, one table a block
    writes = [e["attrs"] for e in regions if e["name"] == "serve.pool_write"]
    blocks = len(engine.pool.buffers)
    if "paged" in options:
        assert {w["dispatches"] for w in writes} <= {
            2 + 5 * blocks + 2, 2 + 5 * blocks + blocks + 2}
    else:
        assert {w["dispatches"] for w in writes} == {1}
    row = 2 * 32 * 2          # K and V, d_model 32, bfloat16
    assert sorted(w["bytes"] for w in writes) == sorted(
        blocks * row * n for n in (4, 6, 7, 12, 5))
    # the lifecycle events that readers filter on keep their form
    assert sum(e["name"] == "tick" for e in events) == engine.tick
    assert sum(e["name"] == "prefill" and e.get("span_name") == "request"
               for e in events) == len(rids)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_an_admission_behind_a_block_in_flight_keeps_its_fetch_target(
        kv_dtype):
    """The async host loop admits while its last block is still to be
    fetched, and that block's ``live`` output IS ``pool.live``: the
    pool's write must leave it readable (it donates the K/V buffers
    alone). Arrivals into an engine that is not full are what reaches
    that state: no retirement has rebound ``pool.live`` in between."""
    m = tiny_lm()
    v = init_lm(m)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 8, size=n).astype(np.int32)
               for n in (5, 9, 3, 12)]
    streams = {}
    for async_host in (False, True):
        engine = ServeEngine(m, v, slots=4, cache_len=32, decode_block=2,
                             kv_dtype=kv_dtype, async_host=async_host)
        write, behind = engine.pool._write, []

        def watched(buffers, positions, live, *rest):
            block = engine._inflight
            behind.append(block is not None and block["live"] is live)
            out = write(buffers, positions, live, *rest)
            assert not live.is_deleted() and not positions.is_deleted()
            return out

        engine.pool._write = watched
        rids, results = [], {}
        for prompt in prompts:
            # one arrival a tick, each behind the last one's first block
            rids.append(engine.submit(prompt, max_new_tokens=8))
            results.update((r.id, r) for r in engine.step())
        results.update(engine.run())
        assert all(results[r].status == "completed" for r in rids)
        assert any(behind) == async_host, behind
        streams[async_host] = [results[r].tokens.tolist() for r in rids]
    assert streams[True] == streams[False]


def test_fetch_region_feeds_the_host_sync_account():
    """``serve.fetch``'s own interval is what ``record_host_sync`` gets,
    in both loops: the fetch is timed once."""
    m = tiny_lm()
    v = init_lm(m)
    for options in ({}, {"async_host": True}):
        engine = ServeEngine(m, v, slots=2, cache_len=32, **options)
        engine.submit(np.arange(5, dtype=np.int32) % 8, max_new_tokens=6)
        engine.run()
        fetches = [e["attrs"]["ms"] for e in engine.recorder.events()
                   if e["name"] == "serve.fetch"]
        assert fetches
        assert engine.metrics.host_sync_wait_s == pytest.approx(
            sum(fetches) / 1e3, abs=1e-5 * len(fetches))


def test_compile_guard_raises_on_violation():
    calls = {"n": 0}

    def count():
        return calls["n"]

    with pytest.raises(AssertionError, match="at most"):
        with compile_guard(count, max_programs=0, label="demo"):
            calls["n"] += 1
    with pytest.raises(AssertionError, match="at least"):
        with compile_guard(count, max_programs=3, min_programs=1,
                           label="demo"):
            pass
    with pytest.raises(ValueError, match="max_programs"):
        with compile_guard(count, max_programs=0, min_programs=1):
            pass


# -- soak / CLI (slow tier) ------------------------------------------------


@pytest.mark.slow
def test_demo_soak():
    from mmlspark_tpu.serve.demo import run_demo

    out = run_demo(slots=3, n_requests=10, max_new_tokens=6,
                   arrivals_per_tick=2, cache_len=48, seed=1)
    assert out["completed"] == 10 and out["expired"] == 0
    assert 1 <= out["decode_compiles"] <= out["decode_block"].bit_length()
    assert out["tokens_generated"] == 60


@pytest.mark.slow
def test_cli_serve_demo_emits_one_json_line():
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, "-m", "mmlspark_tpu", "--cpu-mesh", "4", "serve",
         "--demo", "--slots", "2", "--requests", "4",
         "--max-new-tokens", "4"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd="/root/repo",
    )
    assert res.returncode == 0, res.stderr
    lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1  # ONE parseable JSON line, mirroring bench
    metrics = json.loads(lines[0])
    for key in ("queue_depth_mean", "ttft_ms_mean", "per_token_ms",
                "slot_utilization_mean", "tokens_per_sec"):
        assert key in metrics, key
    assert metrics["completed"] == 4
    assert 1 <= metrics["decode_compiles"] <= (
        metrics["decode_block"].bit_length()
    )
