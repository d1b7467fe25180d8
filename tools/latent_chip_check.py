"""On the chip: the two kernels the latent (MLA) cache brought, compiled,
against their plain-XLA oracles at the ``kanana-2-30b-a3b.report-backlog``
cell's shapes (64 slots x 8,192 rows of 640 lanes, 32 query heads, values
the rows' first 512 columns), then the decode read's device time by block
size at the cell's mean live length, beside the time its bytes and its
operations would take at the chip's peaks. One JSON line a check or a
timing, to stdout and ``chiprun_out/latent_chip_check.jsonl``; exits 1 if
a check is off.

    chiprun -- python tools/latent_chip_check.py [check] [time]

(one chip, about 4 minutes). What ``ops/kv_cache._latent_step``'s block
rests on: run it again before changing that or the kernel's body.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "tools")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from flash_block_timing import traced_ops  # noqa: E402
from mmlspark_tpu.ops.attention import dense_attention  # noqa: E402
from mmlspark_tpu.ops.flash_attention import (  # noqa: E402
    flash_decode_grouped,
    latent_row_write,
)

SLOTS, ROWS, HEADS, WIDE, VALUES, DK = 64, 8192, 32, 640, 512, 576
SCALE = 192 ** -0.5
OUT = ROOT / "chiprun_out" / "latent_chip_check.jsonl"
FAILED = []


def emit(**row) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    OUT.parent.mkdir(exist_ok=True)
    with OUT.open("a") as f:
        f.write(line + "\n")


def operands(slots: int):
    kq, kr = jax.random.split(jax.random.PRNGKey(0))
    lanes = (jnp.arange(WIDE) < DK)  # the pad lanes are nought
    q = jax.random.normal(kq, (slots, 1, HEADS, WIDE), jnp.bfloat16) * lanes
    rows = jax.random.normal(kr, (slots, ROWS, WIDE), jnp.bfloat16) * lanes
    return q.astype(jnp.bfloat16), rows.astype(jnp.bfloat16)


def read(q, rows, lengths, block=None):
    more = {} if block is None else {"block": block}
    return flash_decode_grouped(
        q, rows[:, None], None, lengths, scale=SCALE,
        values_in_keys=VALUES, name="attn_mla_decode", **more)


def check() -> None:
    slots = 8
    q, rows = operands(slots)
    lengths = jnp.asarray([0, 1, 511, 512, 513, 3500, 8191, 8192])
    got = jax.jit(read)(q, rows, lengths)
    keys = rows[:, :, None]
    worst = 0.0
    for i, n in enumerate(np.asarray(lengths)):
        if n == 0:
            want = jnp.zeros_like(got[i])
        else:
            want = dense_attention(
                q[i:i + 1], keys[i:i + 1, :n], keys[i:i + 1, :n, :, :VALUES],
                scale=SCALE)[0]
        worst = max(worst, float(jnp.abs(
            got[i].astype(jnp.float32) - want.astype(jnp.float32)).max()))
    ok = bool(worst <= 0.03)   # bfloat16 weights on sums of unit normals
    if not ok:
        FAILED.append("latent_decode")
    emit(check="latent_decode", gap=worst, limit=0.03, ok=ok)

    at = jnp.asarray([0, 1, 15, 16, 4095, 4096, 8190, 8191])
    new = jax.random.normal(jax.random.PRNGKey(2), (slots, WIDE),
                            jnp.bfloat16)
    want = rows.at[jnp.arange(slots), at].set(new)
    got = jax.jit(latent_row_write, donate_argnums=0)(rows + 0, new, at)
    off = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    if off:
        FAILED.append("latent_row_write")
    emit(check="latent_row_write", gap=off, limit=0.0, ok=not off)


def time_blocks() -> None:
    peak = json.loads((ROOT / "benchmark" / "peaks.json").read_text())[
        jax.devices()[0].device_kind]
    q, rows = operands(SLOTS)
    # the cell's live lengths: prompts 1,024-4,096 log-uniform plus what
    # has been generated of 1,024-4,096, a mean of some 3.5k rows
    rng = np.random.default_rng(0)
    lengths = np.exp(rng.uniform(np.log(1024), np.log(4096), SLOTS)) + \
        rng.uniform(0, 1, SLOTS) * np.exp(
            rng.uniform(np.log(1024), np.log(4096), SLOTS))
    lengths = jnp.asarray(np.minimum(lengths, ROWS).astype(np.int32))
    live = int(lengths.sum())
    nbytes = live * WIDE * 2 + SLOTS * HEADS * (DK + VALUES) * 2
    flops = 2.0 * HEADS * (DK + VALUES) * live
    least = {"bytes_us": round(nbytes / peak["hbm_bytes_per_s"] * 1e6, 1),
             "flops_us": round(flops / peak["flops_per_s"] * 1e6, 1)}
    for name, lens in (("cell", lengths),
                       ("all_rows", jnp.full((SLOTS,), ROWS, jnp.int32))):
        for block in (256, 512, 1024, 2048, 4096):
            ops, _ = traced_ops(jax.jit(lambda q, r, n, b=block: read(
                q, r, n, b)), (q, rows, lens))
            us = sum(v for k, v in ops.items() if k.startswith("attn_mla"))
            row = {"time": "latent_decode", "lengths": name, "block": block,
                   "live_rows": int(lens.sum()), "kernel_us": us,
                   "ops": ops}
            if name == "cell":
                row.update(least, roofline_pct=round(
                    100 * max(least.values()) / us, 1))
            emit(**row)
    at = jnp.minimum(lengths, ROWS - 1)
    new = q[:, 0, 0]
    ops, _ = traced_ops(jax.jit(latent_row_write), (rows, new, at))
    emit(time="latent_row_write", ops=ops)


def main(argv) -> int:
    if jax.devices()[0].platform != "tpu":
        print("latent_chip_check: needs a TPU", file=sys.stderr)
        return 2
    halves = argv or ["check", "time"]
    if "check" in halves:
        check()
    if "time" in halves:
        time_blocks()
    if FAILED:
        print(f"FAILED: {FAILED}", file=sys.stderr)
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
