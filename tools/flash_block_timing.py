"""Device time of the three flash training kernels, and of the forward
kernel at the serving prefills' shapes, by block size: the chip timings
that ``ops/flash_attention.py``'s block chooser rests on.

``chiprun -- python tools/flash_block_timing.py [train] [mimo] [gpt2]``
(one chip, about 3 minutes). Each shape runs under the profiler at every
block that divides its work, and the kernels' durations are read off the
device's own line of the trace (``benchmark/trace_reduce.py``); a line of
JSON a variant goes to stdout and to
``chiprun_out/flash_block_timing.jsonl``. ``control`` rows are XLA's own
attention (``dense_attention``) at the same shape, whole programs.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import trace_reduce  # noqa: E402
from mmlspark_tpu.ops.attention import dense_attention  # noqa: E402
from mmlspark_tpu.ops.flash_attention import flash_attention  # noqa: E402

ITERS = 5
BLOCKS = (128, 256, 512, 1024, None)  # None: the module's own choice
OUT = ROOT / "chiprun_out" / "flash_block_timing.jsonl"


def traced_ops(fn, args) -> tuple[dict, float]:
    """``{operation: mean us}`` in first-start order (a kernel is named
    after the function that calls it: ``jvp__`` the forward,
    ``transpose_jvp___`` dK/dV and then dQ) and the mean device time of a
    whole call, over ITERS traced calls."""
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(ITERS):
                out = fn(*args)
            jax.block_until_ready(out)
        trace = trace_reduce.load(d, 1)
    ops: dict = {}
    for start, end, name in sorted(trace.ops[min(trace.ops)]):
        if trace_reduce.op_family(name) not in trace_reduce.CONTAINERS:
            ops[name] = ops.get(name, 0.0) + (end - start) / 1e3 / ITERS
    return ({k: round(v, 1) for k, v in ops.items() if v >= 1.0},
            round(sum(ops.values()), 1))


def emit(**row) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    OUT.parent.mkdir(exist_ok=True)
    with OUT.open("a") as f:
        f.write(line + "\n")


def rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.bfloat16)


def grad_of(attend):
    def loss(q, k, v, w):
        return (attend(q, k, v).astype(jnp.float32) * w).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def train(b=8, s=1024, h=16, d=64) -> None:
    """train-dp4's per-chip call: forward, dK/dV and dQ, causal."""
    args = (rand(0, b, s, h, d), rand(1, b, s, h, d), rand(2, b, s, h, d),
            rand(3, b, s, h, d).astype(jnp.float32))
    for block in BLOCKS:
        kw = {} if block is None else {"block": block}
        ops, busy = traced_ops(grad_of(lambda q, k, v: flash_attention(
            q, k, v, causal=True, **kw)), args)
        emit(shape=f"train {b}x{s}x{h}x{d}", block=block, busy_us=busy,
             ops=ops)
    ops, busy = traced_ops(grad_of(lambda q, k, v: dense_attention(
        q, k, v, causal=True)), args)
    emit(shape=f"train {b}x{s}x{h}x{d}", block="control", busy_us=busy,
         ops=ops)


def forward(tag, s, h, hk, dk, dv, window=None, sink=False) -> None:
    args = [rand(0, 1, s, h, dk), rand(1, 1, s, hk, dk), rand(2, 1, s, hk, dv)]
    extra = {"window": window} if window else {}
    if sink:
        args.append(jnp.ones((h,), jnp.float32))
    for block in BLOCKS:
        if block is not None and block > s:
            continue
        kw = dict(extra) if block is None else dict(extra, block=block)
        fn = jax.jit(lambda q, k, v, *sk: flash_attention(
            q, k, v, causal=True, sink=sk[0] if sk else None, **kw))
        ops, busy = traced_ops(fn, args)
        emit(shape=f"{tag} {s}", block=block, busy_us=busy, ops=ops)
    if s > 2048:
        return  # 64 heads x 4,096 x 4,096 float32 scores: 4.3 GB
    fn = jax.jit(lambda q, k, v, *sk: dense_attention(
        q, k, v, causal=True, sink=sk[0] if sk else None, **extra))
    ops, busy = traced_ops(fn, args)
    emit(shape=f"{tag} {s}", block="control", busy_us=busy, ops=ops)


def mimo() -> None:
    """mimo-v2-flash's prefill (prompts of 512-3,072 rows): q/k 192 and
    v 128, 64 query heads; full layers 4 KV heads, window layers 8 with a
    sink."""
    for s in (512, 1024, 2048, 4096):  # the prefill's buckets
        forward("mimo_full", s, 64, 4, 192, 128)
        forward("mimo_swa", s, 64, 8, 192, 128, window=128, sink=True)


def gpt2() -> None:
    """gpt2-large's prefill buckets: 20 heads x 64."""
    for s in (32, 64, 128, 256):
        forward("gpt2_large", s, 20, 20, 64, 64)


if __name__ == "__main__":
    if jax.devices()[0].platform != "tpu":
        sys.exit("flash_block_timing.py times the chip: no TPU here")
    if os.path.exists(OUT):
        os.remove(OUT)
    for phase in sys.argv[1:] or ("train", "mimo", "gpt2"):
        {"train": train, "mimo": mimo, "gpt2": gpt2}[phase]()
