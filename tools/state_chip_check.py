"""On the chip, at the ``lfm2-8b-a1b.synth-backlog`` cell's shapes: the
kernel a short convolution's state brought (``conv_decode``: 128 slots x
2,048 channels, 3 taps, the state updated in place), compiled, against its
``jax.numpy`` oracle, and the grouped decode read over this cell's K/V (8
KV heads of 64 two to a row, 4,096 rows, 4 query heads a KV head) against
``dense_attention``; then the cell's fused decode block ALONE, all 128
slots live at the cell's live lengths, its device time a micro-step and
where that time goes by operation: what the cell's expected
``out_tokens_per_s`` is corrected from before the cell runs (``PERF.md``
section 6). One JSON line a check or a timing, to stdout and
``chiprun_out/state_chip_check.jsonl``; exits 1 if a check is off.

    chiprun -- python tools/state_chip_check.py [check] [step]

(one chip; ``check`` about 2 minutes, ``step`` about 6: it makes the
configuration's 8 GB of weights and compiles one decode block.)
"""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from mmlspark_tpu.ops.attention import dense_attention  # noqa: E402
from mmlspark_tpu.ops.conv_decode import (  # noqa: E402
    conv_decode,
    conv_decode_reference,
)
from mmlspark_tpu.ops.flash_attention import flash_decode_grouped  # noqa: E402

WORKLOAD = "lfm2-8b-a1b.synth-backlog"
SLOTS, WIDTH, TAPS = 128, 2048, 3
HEADS, KV_HEADS, HEAD, ROWS = 32, 8, 64, 4096
BLOCK_T, BLOCKS = 8, 6
OUT = ROOT / "chiprun_out" / "state_chip_check.jsonl"
FAILED = []


def emit(**row) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    OUT.parent.mkdir(exist_ok=True)
    with OUT.open("a") as f:
        f.write(line + "\n")


def judge(name: str, gap: float, limit: float) -> None:
    ok = bool(gap <= limit)
    if not ok:
        FAILED.append(name)
    emit(check=name, gap=gap, limit=limit, ok=ok)


def conv_check(slots: int = SLOTS, width: int = WIDTH,
               interpret: bool | None = None) -> float:
    """The widest gap of the compiled kernel to its oracle, output and
    state, over slots of which a third are dead."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    proj = jax.random.normal(k[0], (slots, 3 * width), jnp.bfloat16)
    state = jax.random.normal(k[1], (slots, (TAPS - 1) * width),
                              jnp.bfloat16)
    taps = 0.5 * jax.random.normal(k[2], (TAPS, width), jnp.float32)
    live = jax.random.uniform(k[3], (slots,)) > 0.33
    want_y, want_state = conv_decode_reference(proj, state, taps, live)
    y, new = jax.jit(
        lambda p, s, t, lv: conv_decode(p, s, t, lv, interpret=interpret),
        donate_argnums=1)(proj, state + 0, taps, live)
    f32 = jnp.float32
    return max(float(jnp.abs(y.astype(f32) - want_y.astype(f32)).max()),
               float(jnp.abs(new.astype(f32)
                             - want_state.astype(f32)).max()))


def cell_lengths(slots: int = SLOTS):
    """The cell's live lengths: prompts log-uniform 256-1,024 plus what
    has been generated of answers log-uniform 512-3,072."""
    rng = np.random.default_rng(0)

    def drawn(lo, hi):
        return np.exp(rng.uniform(np.log(lo), np.log(hi), slots))

    lengths = drawn(256, 1024) + rng.uniform(0, 1, slots) * drawn(512, 3072)
    return np.minimum(lengths, ROWS - 64).astype(np.int32)


def check() -> None:
    # output and state are bfloat16 roundings of the same float32 sums
    judge("conv_decode", conv_check(), 0.0)
    slots = 8
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k[0], (slots, 1, HEADS, HEAD), jnp.bfloat16)
    keys = jax.random.normal(k[1], (slots, ROWS, KV_HEADS, HEAD),
                             jnp.bfloat16)
    values = jax.random.normal(k[2], (slots, ROWS, KV_HEADS, HEAD),
                               jnp.bfloat16)

    def packed(rows):           # two adjacent heads side by side in a row
        return jnp.moveaxis(rows.reshape(slots, ROWS, KV_HEADS // 2,
                                         2 * HEAD), 1, 2)

    read = jax.jit(lambda q, k, v, n: flash_decode_grouped(
        q, k, v, n, name="attn_full_decode"))
    for name, lens in (("mixed", [0, 1, 255, 256, 1500, 3071, 4095, 4096]),
                       ("dead", [0] * slots), ("full", [ROWS] * slots)):
        lengths = jnp.asarray(lens)
        got = read(q, packed(keys), packed(values), lengths)
        worst = 0.0
        for i, n in enumerate(lens):
            want = jnp.zeros_like(got[i]) if n == 0 else dense_attention(
                q[i:i + 1], keys[i:i + 1, :n], values[i:i + 1, :n])[0]
            worst = max(worst, float(jnp.abs(
                got[i].astype(jnp.float32) - want.astype(jnp.float32)).max()))
        # bfloat16 weights on sums of unit normals
        judge(f"full_decode.{name}", worst, 0.03)


def step(workload: str = WORKLOAD, root: str = str(ROOT)) -> None:
    """The cell's decode block alone: ``BLOCKS`` blocks of ``BLOCK_T``
    micro-steps over all slots live at the cell's lengths, traced."""
    from benchmark import family, run, serving, trace_reduce
    from mmlspark_tpu.models.generate import make_decode_block
    from mmlspark_tpu.serve.cache_pool import SlotCachePool

    manifest = run.load_json(root, "BENCHMARK.json")
    files = run.cell_files(manifest, workload, root)
    run.compile_cache()
    fam = family.resolve(files["config"], files["mix"]["kind"],
                         files["control_mode"], root)
    engine = files["config"]["program"]["engine"]
    slots = int(engine["slots"])
    variables = serving.build_weights(fam, 35)
    graph = serving.build_graph(fam)
    pool = SlotCachePool(graph, variables, slots, int(engine["cache_len"]))
    lengths = np.minimum(cell_lengths(slots),
                         int(engine["cache_len"]) - 2 * BLOCK_T * BLOCKS)
    block = jax.jit(make_decode_block(graph), static_argnums=(7,),
                    donate_argnums=(1, 2, 3))
    state = (pool.buffers, jnp.asarray(lengths), jnp.ones((slots,), bool))
    tok = jnp.arange(slots, dtype=jnp.int32)
    rem = jnp.full((slots,), 1 << 20, jnp.int32)
    eos = jnp.full((slots,), -1, jnp.int32)

    def run_block(state, tok):
        toks, live, buffers, pos, stats = block(variables, *state, tok, rem,
                                                eos, BLOCK_T)
        return (buffers, pos, live), toks[:, -1], stats

    for _ in range(2):
        state, tok, stats = run_block(state, tok)
    jax.block_until_ready(tok)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(BLOCKS):
                state, tok, stats = run_block(state, tok)
            jax.block_until_ready(tok)
        trace = trace_reduce.load(d, 1)
    steps = BLOCKS * BLOCK_T
    mods = trace.module_events("decode_block")
    step_ms = sum(e - s for s, e, _ in mods) / 1e6 / steps
    ops: dict = {}
    for start, end, name in trace.ops[min(trace.ops)]:
        kind = trace_reduce.op_family(name)
        if kind not in trace_reduce.CONTAINERS:
            ops[kind] = ops.get(kind, 0.0) + (end - start) / 1e3 / steps
    top = dict(sorted(((k, round(v, 1)) for k, v in ops.items()),
                      key=lambda kv: -kv[1])[:16])
    live = int(np.mean(lengths)) + 2 * BLOCK_T + BLOCKS * BLOCK_T // 2
    spec = {"expert_pairs": float(np.mean(stats["expert_pairs"]) / BLOCK_T),
            "experts_hit": float(np.mean(stats["experts_hit"]) / BLOCK_T)}
    least_ms = fam.counts.decode_step_bytes(
        fam.sz, [live] * slots, spec) / 819e9 * 1e3
    emit(time="decode_step", slots=slots, mean_live_rows=live,
         step_ms=round(step_ms, 3), least_ms=round(least_ms, 3),
         hbm_roofline_pct=round(100 * least_ms / step_ms, 1),
         tokens_per_s_if_never_idle=round(slots / step_ms * 1e3, 1),
         us_a_micro_step_by_operation=top, **spec)


def main(argv) -> int:
    if jax.devices()[0].platform != "tpu":
        print("state_chip_check: needs a TPU", file=sys.stderr)
        return 2
    parts = argv or ["check", "step"]
    if "check" in parts:
        check()
    if "step" in parts:
        step()
    if FAILED:
        print(f"FAILED: {FAILED}", file=sys.stderr)
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
