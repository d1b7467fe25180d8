#!/usr/bin/env bash
# One-command CI gate: lint -> install check -> tests -> examples -> docgen.
# The `runme` analog (reference runme:1-50 / sbt full-build at
# src/project/build.scala:84-93: scalastyle -> compile -> test -> package
# -> codegen). Usage:
#   tools/ci.sh            # full run
#   tools/ci.sh fast       # lint + tests only
#   PROC_SHARD=1/3 tools/ci.sh   # shard the example suite (harness.py)
set -euo pipefail
cd "$(dirname "$0")/.."

step() { echo; echo "=== $1 ==="; }

step "lint (scalastyle analog)"
if command -v ruff >/dev/null 2>&1; then
  ruff check .
else
  python tools/lint.py
fi

step "package import check"
python -c "import mmlspark_tpu; print('mmlspark_tpu', 'stages:',
len(mmlspark_tpu.all_stages()))"

step "native ops: build from source (no committed binaries)"
# .so files are gitignored; delete any stale build products so the C++
# ops compile fresh from the shipped sources, then prove both load —
# the parity tests (test_ctf_native.py, decode tests) then run against
# exactly these binaries (NativeLoader.java packaging analog)
rm -f mmlspark_tpu/ops/native/*.so
python - <<'PY'
from mmlspark_tpu.ops import native_build
for name in ("decode", "ctf"):
    lib = native_build.load_native(name)
    assert lib is not None, f"source build failed for native lib {name!r}"
print("native libs built from source: decode, ctf")
PY

step "unit + integration tests (8-device CPU mesh via tests/conftest.py)"
if [ "${1:-}" = "fast" ]; then
  python -m pytest tests/ -q
else
  # the example tier runs ONCE: harness.py below covers it, so the
  # in-pytest copy is skipped here (it remains for bare `pytest tests/`)
  python -m pytest tests/ -q --ignore=tests/test_examples.py \
    --ignore=tests/test_examples_long_context.py
fi

if [ "${1:-}" != "fast" ]; then
  step "example suite (notebook-parity flows)"
  python examples/harness.py

  step "docker image (build if a daemon exists; else execute the pip RUN
line in a clean venv)"
  docker_built=no
  if command -v docker >/dev/null 2>&1; then
    # a daemon without egress (or without the base image cached) cannot
    # pull the base layer — fall through to the venv proof instead of
    # failing the whole gate on an environment limitation
    if docker build -t mmlspark-tpu-ci -f tools/docker/Dockerfile .; then
      docker_built=yes
    else
      echo "WARNING: docker build failed (no egress / base image" \
           "unavailable?) — falling back to the venv RUN-line proof"
    fi
  fi
  if [ "$docker_built" = no ]; then
    # no daemon in this environment: prove the Dockerfile's pip RUN line
    # executes by running it against a clean venv. The baked environment's
    # site-packages are linked in via a .pth, playing the role of the
    # image layer's earlier `pip install jax` (this runner may itself be
    # a venv, so --system-site-packages would miss them); the package +
    # its [test] extra must then resolve offline and import from OUTSIDE
    # the repo.
    venv_dir=$(mktemp -d)/venv
    python -m venv "$venv_dir"
    baked=$(python -c "import sysconfig; print(sysconfig.get_paths()['purelib'])")
    vsite=$("$venv_dir/bin/python" -c "import sysconfig; print(sysconfig.get_paths()['purelib'])")
    echo "$baked" > "$vsite/_baked_deps.pth"
    "$venv_dir/bin/pip" install --no-cache-dir --no-index \
      --no-build-isolation --quiet ".[test]"
    (cd / && "$venv_dir/bin/python" -c \
      "import mmlspark_tpu; print('docker RUN-line venv check:',
len(mmlspark_tpu.all_stages()), 'stages')")
    rm -rf "$(dirname "$venv_dir")"
  fi

  step "decode-block parity gate (fused blocks == generate(), every T)"
  python -m pytest tests/test_decode_block.py -q

  step "sharded serving parity gate (mesh engine == generate(), 2x2)"
  python -m pytest tests/test_serve_sharded.py -q

  step "serving resilience gate (fault injection / quarantine / chaos soak)"
  python -m pytest tests/test_serve_faults.py -q

  step "paged KV-cache gate (allocator / prefix cache / paged-decode parity)"
  python -m pytest tests/test_paging.py -q

  step "supervisor gate (replica failover / hedging / drain chaos drills)"
  python -m pytest tests/test_serve_supervisor.py -q

  step "quantized decode gate (int8 KV + weight-only int8 vs the bf16 oracle)"
  python -m pytest tests/test_quantized_serve.py -q

  step "chunked prefill + async host gate (parity, compile pins, sync budget)"
  python -m pytest tests/test_chunked_async.py -q

  step "disagg gate (prefill/decode fleet: hand-off, prefix index, autoscaler)"
  python -m pytest tests/test_serve_fleet.py -q
  python tools/check_metrics_schema.py --disagg

  step "multi-model gate (LM + stateless zoo deployments, one engine)"
  python -m pytest tests/test_multimodel.py -q
  python tools/check_metrics_schema.py --multi-model

  step "training resilience gate (fault drills / atomic resume / quarantine)"
  python -m pytest tests/test_train_resilience.py -q
  python tools/check_metrics_schema.py --train

  step "integrity gate (SDC detection / checksummed hand-offs / verified restore)"
  python -m pytest tests/test_integrity.py tests/test_faults_coverage.py -q
  # corrupt drill through the real CLI: a seeded train.step bit-flip
  # must be caught, quarantined, and replay-adjudicated (the --train
  # schema gate above pins the full metric contract; this run pins the
  # plane end-to-end at a different audit cadence)
  integrity_tmp=$(mktemp -d)
  JAX_PLATFORMS=cpu python -m mmlspark_tpu --cpu-mesh 4 train \
    --epochs 2 --samples 96 --batch-size 32 --seed 0 \
    --checkpoint-every 2 --audit-every 3 \
    --faults 'seed=3,train.step:corrupt=0.2' \
    --telemetry-dir "$integrity_tmp" \
    --checkpoint-dir "$integrity_tmp/ck" \
    | python -c '
import json, sys
md = json.load(sys.stdin)
assert md["train.integrity.audits"] >= 1, md
assert md["train.integrity.sdc_suspected"] >= 1, md
print("integrity drill: OK —",
      md["train.integrity.sdc_suspected"], "bit-flip(s) caught across",
      md["train.integrity.audits"], "audit(s)")
'
  rm -rf "$integrity_tmp"

  step "telemetry schema gate (serve --demo artifacts)"
  python tools/check_metrics_schema.py

  step "distributed tracing gate (TelemetryHub merge / flow arrows / alerts)"
  python -m pytest tests/test_tracehub.py -q
  python tools/check_metrics_schema.py --tracing

  step "bench regression gate (selftest vs the recorded BENCH history)"
  # proves the tolerance-band logic on the REAL history: the newest
  # usable entry must pass, a 25% injected slowdown must fail — no
  # fresh bench run needed. Gating a fresh run:
  #   python bench.py > /tmp/fresh.json \
  #     && python tools/bench_regression.py /tmp/fresh.json
  python tools/bench_regression.py --selftest

  step "trace-export smoke (serve --trace-out -> Perfetto-loadable JSON)"
  trace_tmp=$(mktemp -d)
  JAX_PLATFORMS=cpu python -m mmlspark_tpu serve --demo --slots 2 \
    --requests 3 --max-new-tokens 4 --trace-out "$trace_tmp/trace.json" \
    > /dev/null
  python - "$trace_tmp/trace.json" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
evs = doc["traceEvents"]
assert evs and all("ph" in e and "ts" in e for e in evs), "malformed trace"
assert any(e["ph"] == "X" and e["name"].startswith("request ") for e in evs)
print("trace-export smoke:", len(evs), "events, Chrome trace-event JSON ok")
PY
  rm -rf "$trace_tmp"

  step "docgen"
  python tools/docgen.py

  step "bench smoke (one JSON line, CPU smoke scale, labelled as such)"
  # CI has no chip: ask for the smoke scale explicitly. bench.py itself
  # exits 2 without a TPU and never lands on the CPU on its own.
  JAX_PLATFORMS=cpu MMLTPU_BENCH_CPU_SMOKE=1 python bench.py
fi

echo
echo "CI green."
