"""Compile the serve and train kernels for a TPU v5e that is described,
not attached (the on-chip-measurement guide, section 2 step 3).

Interpret mode cannot see what the chip's compiler refuses: SMEM and
VMEM budgets, tile alignment. These cases hold every Pallas kernel of
the main path, and the two serve step programs, to the GPT-2-small
shapes ``chip_smoke.py`` runs — so a kernel that stops compiling at a
real size fails here, at no chip time. Nothing executes; a compile
that passes is not a chip run.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from mmlspark_tpu.ops.flash_attention import (
    cache_row_write,
    flash_attention,
    flash_decode,
    flash_decode_grouped,
    latent_row_write,
    paged_flash_decode,
)
from mmlspark_tpu.ops.grouped_matmul import grouped_matmul
from mmlspark_tpu.parallel.expert import held_tiles
from mmlspark_tpu.testing.compile_guard import kernel_grids

HEADS, HEAD_DIM, CACHE = 12, 64, 1024
GPT2_SMALL = dict(vocab_size=50257, d_model=768, heads=HEADS, depth=12,
                  d_ff=3072, max_len=CACHE)
# the benchmark's serving configuration (gpt2-large.chat-backlog: 20 heads
# x 64, 16 slots x 1,024); its 36 layers are cut to 2 here for time
GPT2_LARGE = dict(vocab_size=50257, d_model=1280, heads=20, depth=2,
                  d_ff=5120, max_len=CACHE)
LARGE_SLOTS = 16


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e chip; the persistent compilation
    cache is off around these compiles (an entry written for a described
    device cannot be read back without one, and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, no topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _qkv(b, s):
    return (jax.ShapeDtypeStruct((b, s, HEADS, HEAD_DIM), jnp.bfloat16),) * 3


def _attention(**kw):
    return (lambda q, k, v: flash_attention(q, k, v, interpret=False, **kw),
            _qkv(2, CACHE))


def _attention_bwd(shape=(2, CACHE, HEADS, HEAD_DIM)):
    """Forward and both backward kernels at the block the module chooses."""
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    return (jax.grad(loss, argnums=(0, 1, 2)),
            (jax.ShapeDtypeStruct(shape, jnp.bfloat16),) * 3)


def _decode(slots, int8, cache=CACHE):
    kv = jax.ShapeDtypeStruct(
        (slots, cache, HEADS, HEAD_DIM), jnp.int8 if int8 else jnp.bfloat16
    )
    args = [jax.ShapeDtypeStruct((slots, 1, HEADS, HEAD_DIM), jnp.bfloat16),
            kv, kv, jax.ShapeDtypeStruct((slots,), jnp.int32)]
    if not int8:
        return (lambda q, k, v, n: flash_decode(q, k, v, n, interpret=False),
                args)
    sc = jax.ShapeDtypeStruct((slots, HEADS), jnp.float32)
    return (lambda q, k, v, n, ks, vs: flash_decode(
        q, k, v, n, k_scale=ks, v_scale=vs, interpret=False),
        args + [sc, sc])


def _paged(slots, page_size, int8):
    """The pool's own worst-case geometry: every slot fully paged plus
    the trash page — 64 slots at page_size 16 is the 4,097-page case,
    64 slots at page_size 8 the 64 x 128 page table."""
    max_pages = CACHE // page_size
    num_pages = slots * max_pages + 1
    pages = jax.ShapeDtypeStruct(
        (num_pages, HEADS, page_size, HEAD_DIM),
        jnp.int8 if int8 else jnp.bfloat16,
    )
    args = [jax.ShapeDtypeStruct((slots, 1, HEADS, HEAD_DIM), jnp.bfloat16),
            pages, pages, jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots, max_pages), jnp.int32)]
    if not int8:
        return (lambda q, k, v, n, pt: paged_flash_decode(
            q, k, v, n, pt, interpret=False), args)
    sc = jax.ShapeDtypeStruct((num_pages, HEADS), jnp.float32)
    return (lambda q, k, v, n, pt, ks, vs: paged_flash_decode(
        q, k, v, n, pt, k_scale=ks, v_scale=vs, interpret=False),
        args + [sc, sc])


def _gpt2(config=GPT2_SMALL):
    from mmlspark_tpu.models import build_model

    graph = build_model("transformer_lm", **config)
    assert graph.extra["attn_impl"] == "flash"  # is_tpu patched -> auto
    variables = jax.eval_shape(
        graph.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    return graph, variables


def _pool_shapes(graph, variables, slots, **options):
    """The entries ``SlotCachePool`` would hold for ``slots`` slots, as
    shapes: a ONE-slot pool is built and its slot dimension widened, so
    the layout under test is the pool's own choice."""
    from mmlspark_tpu.serve.cache_pool import SlotCachePool

    pool = SlotCachePool(graph, variables, 1, CACHE, **options)
    return pool, jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((slots,) + a.shape[1:], a.dtype),
        pool.buffers)


def _decode_block(slots=8, t=32, config=GPT2_SMALL):
    from mmlspark_tpu.models.generate import make_decode_block

    graph, variables = _gpt2(config)
    _pool, buffers = _pool_shapes(graph, variables, slots)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    block = make_decode_block(graph)
    return (lambda v, b, pos, lv, tok, rem, eos: block(
        v, b, pos, lv, tok, rem, eos, t),
        [variables, buffers, ints, live, ints, ints, ints])


def _prefill():
    from mmlspark_tpu.models.generate import _cached_apply, init_cache

    graph, variables = _gpt2()

    def prefill(v, prompt):
        cache = init_cache(graph, v, 1, prompt.shape[1])
        return _cached_apply(graph, v, prompt, cache, 0)

    return prefill, [variables, jax.ShapeDtypeStruct((1, CACHE), jnp.int32)]


# -- hybrid_lm's kernels at the benchmark cell's shapes (MiMo-V2-Flash:
# 64 query heads, q/k 192 and v 128, 4 KV heads over 4,096 rows or 8 over
# a ring of 128, 64 slots; 16 held experts of 4,096 x 2,048)


def _bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def _hybrid_decode(hk, rows, sink):
    args = [_bf16(64, 1, 64, 192), _bf16(64, hk, rows, 192),
            _bf16(64, hk, rows, 128),
            jax.ShapeDtypeStruct((64,), jnp.int32)]
    if not sink:
        return (lambda q, k, v, n: flash_decode_grouped(
            q, k, v, n, interpret=False), args)
    return (lambda q, k, v, n, s: flash_decode_grouped(
        q, k, v, n, sink=s, interpret=False),
        args + [jax.ShapeDtypeStruct((64,), jnp.float32)])


def _hybrid_row_write(hk, rows):
    return (lambda k, v, kn, vn, at: cache_row_write(
        k, v, kn, vn, at, interpret=False),
        [_bf16(64, hk, rows, 192), _bf16(64, hk, rows, 128),
         _bf16(64, hk, 192), _bf16(64, hk, 128),
         jax.ShapeDtypeStruct((64,), jnp.int32)])


def _hybrid_forward(hk, window, block, s=4096):
    args = [_bf16(1, s, 64, 192), _bf16(1, s, hk, 192),
            _bf16(1, s, hk, 128)]
    if window is None:
        return (lambda q, k, v: flash_attention(
            q, k, v, causal=True, block=block, interpret=False), args)
    return (lambda q, k, v, sink: flash_attention(
        q, k, v, causal=True, window=window, sink=sink, block=block,
        interpret=False),
        args + [jax.ShapeDtypeStruct((64,), jnp.float32)])


# kanana-2-30b-a3b.report-backlog: 64 slots x 8,192 latent rows of 576
# numbers held in 640 lanes, 32 query heads on the one stream
LATENT = dict(slots=64, rows=8192, heads=32, wide=640, values=512)


def _latent_decode(slots=LATENT["slots"]):
    return (lambda q, rows, n: flash_decode_grouped(
        q, rows, None, n, scale=192 ** -0.5,
        values_in_keys=LATENT["values"], interpret=False),
        [_bf16(slots, 1, LATENT["heads"], LATENT["wide"]),
         _bf16(slots, 1, LATENT["rows"], LATENT["wide"]),
         jax.ShapeDtypeStruct((slots,), jnp.int32)])


def _latent_row_write(slots=LATENT["slots"]):
    return (lambda rows, new, at: latent_row_write(
        rows, new, at, interpret=False),
        [_bf16(slots, LATENT["rows"], LATENT["wide"]),
         _bf16(slots, LATENT["wide"]),
         jax.ShapeDtypeStruct((slots,), jnp.int32)])


def _latent_forward(s=4096):
    """The expanded prefill: 32 heads of 192 against 32 of 192 / 128."""
    return (lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False),
        [_bf16(1, s, 32, 192), _bf16(1, s, 32, 192), _bf16(1, s, 32, 128)])


def _grouped(tokens, held, top_k, experts, k, n):
    """One grouped product of an expert layer at the row tile
    ``held_tiles`` chooses for a step of ``tokens`` tokens and the weight
    block ``grouped_matmul`` chooses for that tile."""
    tm, tiles = held_tiles(tokens, held, top_k, experts)
    return (lambda x, w, g, live: grouped_matmul(
        x, w, g, live, tm=tm, interpret=False),
        [_bf16(tiles * tm, k), _bf16(held, k, n),
         jax.ShapeDtypeStruct((tiles,), jnp.int32),
         jax.ShapeDtypeStruct((), jnp.int32)])


def _large_decode(slots=LARGE_SLOTS):
    """gpt2-large's decode read as the dense bf16 pool holds its rows:
    20 heads x 64 two to a row, all ten row-heads a grid step."""
    return (lambda q, k, v, n: flash_decode_grouped(
        q, k, v, n, interpret=False),
        [_bf16(slots, 1, 20, 64), _bf16(slots, 10, CACHE, 128),
         _bf16(slots, 10, CACHE, 128),
         jax.ShapeDtypeStruct((slots,), jnp.int32)])


def _large_row_write(slots=LARGE_SLOTS):
    return (lambda k, v, kn, vn, at: cache_row_write(
        k, v, kn, vn, at, interpret=False),
        [_bf16(slots, 10, CACHE, 128), _bf16(slots, 10, CACHE, 128),
         _bf16(slots, 10, 128), _bf16(slots, 10, 128),
         jax.ShapeDtypeStruct((slots,), jnp.int32)])


CASES = {
    "gpt2_large_decode_grouped_16": _large_decode,
    "gpt2_large_decode_grouped_32": lambda: _large_decode(32),
    "gpt2_large_row_write_16": _large_row_write,
    "hybrid_decode_full_4096": lambda: _hybrid_decode(4, 4096, False),
    "hybrid_decode_ring_128_sink": lambda: _hybrid_decode(8, 128, True),
    "hybrid_row_write_full": lambda: _hybrid_row_write(4, 4096),
    "hybrid_row_write_ring": lambda: _hybrid_row_write(8, 128),
    "hybrid_fwd_full_4096": lambda: _hybrid_forward(4, None, 512),
    "hybrid_fwd_swa_sink_4096": lambda: _hybrid_forward(8, 128, 512),
    "hybrid_fwd_swa_sink_256": lambda: _hybrid_forward(8, 128, 128, s=256),
    # the cell's widest prompt, at the block the module chooses
    "hybrid_fwd_full_3072_chosen": lambda: _hybrid_forward(
        4, None, None, s=3072),
    "hybrid_fwd_swa_sink_3072_chosen": lambda: _hybrid_forward(
        8, 128, None, s=3072),
    "latent_decode_8192": _latent_decode,
    "latent_row_write_8192": _latent_row_write,
    "latent_fwd_4096_chosen": _latent_forward,
    # reason-backlog: 64 slots and a bucket of 2,048, 8 of 256, 16 held
    "grouped_matmul_decode_up": lambda: _grouped(
        64, 16, 8, 256, 4096, 2048),
    "grouped_matmul_decode_down": lambda: _grouped(
        64, 16, 8, 256, 2048, 4096),
    "grouped_matmul_prefill_up": lambda: _grouped(
        2048, 16, 8, 256, 4096, 2048),
    # synth-backlog: 128 slots and a bucket of 512, 4 of 32, all held
    "grouped_matmul_synth_decode_up": lambda: _grouped(
        128, 32, 4, 32, 2048, 1792),
    "grouped_matmul_synth_decode_down": lambda: _grouped(
        128, 32, 4, 32, 1792, 2048),
    "grouped_matmul_synth_prefill_up": lambda: _grouped(
        512, 32, 4, 32, 2048, 1792),
    # report-backlog: 64 slots and a chunk of 2,048, 6 of 128, 16 held
    "grouped_matmul_report_decode_up": lambda: _grouped(
        64, 16, 6, 128, 2048, 768),
    "grouped_matmul_report_decode_down": lambda: _grouped(
        64, 16, 6, 128, 768, 2048),
    "grouped_matmul_report_prefill_down": lambda: _grouped(
        2048, 16, 6, 128, 768, 2048),
    # the widest row tile beside the widest contraction
    "grouped_matmul_tile_512_up": lambda: _grouped(
        8192, 16, 8, 256, 4096, 2048),
    "flash_fwd_full": lambda: _attention(),
    "flash_fwd_causal": lambda: _attention(causal=True),
    "flash_fwd_windowed": lambda: _attention(causal=True, window=256),
    "flash_bwd_causal": _attention_bwd,
    # gpt2-medium.train-dp4's call on one chip: 8 sequences x 1,024, 16 x 64
    "flash_train_dp4_grad_chosen": lambda: _attention_bwd((8, 1024, 16, 64)),
    "flash_decode_bf16_8": lambda: _decode(8, False),
    "flash_decode_bf16_64": lambda: _decode(64, False),
    # generate()'s cache is prompt + new tokens long: 700 + 48 has no
    # divisor that is whole sublanes, so the read takes the padded layout
    "flash_decode_bf16_len748": lambda: _decode(1, False, cache=748),
    "flash_decode_int8_8": lambda: _decode(8, True),
    "flash_decode_int8_64": lambda: _decode(64, True),
    "paged_bf16_ps8_1025_pages": lambda: _paged(8, 8, False),
    "paged_int8_ps8_1025_pages": lambda: _paged(8, 8, True),
    "paged_bf16_ps8_table_64x128": lambda: _paged(64, 8, False),
    "paged_bf16_ps16_4097_pages": lambda: _paged(64, 16, False),
    "paged_int8_ps16_4097_pages": lambda: _paged(64, 16, True),
    "paged_bf16_ps128": lambda: _paged(64, 128, False),
    "paged_int8_ps128": lambda: _paged(64, 128, True),
    "gpt2_small_decode_block_t32": _decode_block,
    "gpt2_small_prefill_1x1024": _prefill,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, chip, monkeypatch):
    # code that asks "is this a TPU" sees the CPU here and would take
    # its dense / interpret branch; the test steers it, not an option
    monkeypatch.setattr("mmlspark_tpu.core.env.is_tpu", lambda: True)
    fn, args = CASES[case]()
    compiled = jax.jit(fn).lower(*_on(chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case,calls,bh,s,d,dv", [
    ("flash_train_dp4_grad_chosen", 3, 8 * 16, 1024, 64, 64),
    ("hybrid_fwd_full_3072_chosen", 1, 64, 3072, 192, 128),
    ("hybrid_fwd_swa_sink_3072_chosen", 1, 64, 3072, 192, 128),
])
def test_flash_grid_is_the_choosers(case, calls, bh, s, d, dv, monkeypatch):
    """The kernels of a call that passes no ``block`` take the grid that
    ``_flash_block`` gives their shapes: each of train-dp4's three
    calls one step a head, 128, where a block of 128 took 8,192."""
    from mmlspark_tpu.ops import flash_attention as fa

    monkeypatch.setattr("mmlspark_tpu.core.env.is_tpu", lambda: True)
    fn, args = CASES[case]()
    blocks = -(-s // fa._flash_block(s, d, dv, 2))
    grids = kernel_grids(fn, *args)
    assert blocks == -(-s // 1024)
    assert grids == [(bh, blocks, blocks)] * calls


@pytest.mark.parametrize("case,static", [
    ("latent_decode_8192", None),
    ("hybrid_decode_full_4096", None),
    ("hybrid_decode_ring_128_sink", (64 * 8, 1)),
    ("gpt2_large_decode_grouped_16", (16, 1, 4)),
])
def test_decode_grid_is_a_work_list_where_blocks_can_be_dead(case, static,
                                                             chip):
    """The grouped decode read at the cells' shapes: a group of 8 query
    heads or more over several blocks (report-backlog's latent rows,
    reason-backlog's full layers) takes ONE grid axis whose bound is
    traced, the list of live blocks, and compiles so for the described
    v5e with its two prefetched lists (64 x 4 x 8 entries each beside the
    lengths: SMEM holds them); a ring's one block and gpt2-large's
    several-heads-a-step grid stay static."""
    fn, args = CASES[case]()
    (grid,) = kernel_grids(fn, *args)
    if static:
        assert grid == static
        return
    assert len(grid) == 1 and not isinstance(grid[0], int)
    text = jax.jit(fn).lower(*_on(chip, args)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("case,blocks", [
    # (N blocks, contraction blocks) of the weight block chosen by bytes
    ("grouped_matmul_synth_decode_up", (2, 1)),      # 2,048 x 896: 3.5 MiB
    ("grouped_matmul_synth_decode_down", (2, 1)),    # 1,792 x 1,024
    ("grouped_matmul_report_decode_up", (1, 1)),     # an expert whole: 3 MiB
    ("grouped_matmul_report_decode_down", (1, 1)),
    ("grouped_matmul_decode_up", (4, 1)),            # 4,096 x 512: 4 MiB
    ("grouped_matmul_decode_down", (4, 1)),          # 2,048 x 1,024
])
def test_grouped_grid_is_bounded_by_the_live_tiles(case, blocks):
    """The expert products at the three routed cells' decode shapes: the
    row-tile axis of the grid is TRACED (the live tiles: a dead tile
    takes no step), the contraction is one block, and the matrix is cut
    along N into blocks of 3 to 4 MiB."""
    fn, args = CASES[case]()
    (grid,) = kernel_grids(fn, *args)
    assert not isinstance(grid[0], int) and tuple(grid[1:]) == blocks


def _on(chip, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree)


def _pool_copies(text: str, shape: tuple) -> list:
    """The ``copy`` and ``transpose`` instructions of an optimised HLO
    module whose result has a pool array's dimensions, in any order."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* (copy|copy-start|transpose)\(",
                      line)
        if m and sorted(int(n) for n in m.group(1).split(",")) == sorted(
                shape):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("config,slots,kv_dtype", [
    (GPT2_SMALL, 8, "bf16"), (GPT2_SMALL, 8, "int8"),
    (GPT2_LARGE, LARGE_SLOTS, "bf16"),
], ids=["gpt2_small-bf16", "gpt2_small-int8", "gpt2_large-bf16"])
def test_pool_write_updates_the_pool_in_place_on_v5e(config, slots,
                                                     kv_dtype, chip,
                                                     monkeypatch):
    """The dense pool's jitted prefill write at real shapes, a 256-row
    bucket, into the layout the pool itself chose: every pool buffer is
    aliased to its output and the temporaries stay under ONE buffer's
    size, so the donation took and no copy of the pool exists beside the
    pool (a failed one would add the pool's bytes to the chip's peak on
    every admission)."""
    import math

    monkeypatch.setattr("mmlspark_tpu.core.env.is_tpu", lambda: True)
    graph, variables = _gpt2(config)
    pool, buffers = _pool_shapes(graph, variables, slots, kv_dtype=kv_dtype)
    heads = config["heads"]
    source = jax.ShapeDtypeStruct((1, 256, heads, HEAD_DIM), jnp.bfloat16)
    cache = {name: (source, source) for name in buffers}
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = pool._write.lower(*_on(chip, (
        buffers, jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.bool_), cache, scalar, scalar,
        scalar,
    ))).compile()
    memory = compiled.memory_analysis()
    kv = buffers["block0"][0]
    one = math.prod(kv.shape) * kv.dtype.itemsize
    assert one == slots * CACHE * heads * HEAD_DIM * kv.dtype.itemsize
    assert memory.alias_size_in_bytes >= 2 * config["depth"] * one
    assert memory.temp_size_in_bytes < one
    assert not _pool_copies(compiled.as_text(), kv.shape)


def test_gpt2_large_decode_block_reads_the_pool_where_it_lies(
        chip, monkeypatch):
    """The fused decode block at gpt2-large's own shapes (20 heads x 64,
    16 slots x 1,024; 2 of its 36 layers), over the entries the pool
    itself lays out: it compiles for the described v5e, its optimised
    HLO holds no ``copy`` or ``transpose`` of a pool-sized operand (the
    rows are written and read where they lie: heads of 64 two to a row
    of 128 lanes, which the chip holds as the kernels read them), the
    pool is updated in place, and the temporaries stay under 1 GB."""
    from mmlspark_tpu.ops.kv_cache import HeadMajorKV

    monkeypatch.setattr("mmlspark_tpu.core.env.is_tpu", lambda: True)
    fn, args = _decode_block(slots=LARGE_SLOTS, t=4, config=GPT2_LARGE)
    buffers = args[1]
    for entry in buffers.values():
        assert isinstance(entry, HeadMajorKV)
        assert entry.k.shape == (LARGE_SLOTS, 10, CACHE, 128)
    compiled = jax.jit(fn, donate_argnums=(1, 2, 3)).lower(
        *_on(chip, args)).compile()
    text = compiled.as_text()
    one = LARGE_SLOTS * 10 * CACHE * 128 * 2
    assert not _pool_copies(text, (LARGE_SLOTS, 10, CACHE, 128))
    # the kernel under the name the decode metrics look for, once a layer
    assert len(set(re.findall(r"%(attn\.\d+) = ", text))) == 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * one
    assert memory.temp_size_in_bytes < 1 << 30


def test_pool_refuses_a_page_table_the_kernel_cannot_hold():
    """The one size the kernel cannot be made to compile at — a page
    table past scalar memory — is refused when the pool is built, with
    the limit named; it never reaches a dispatch."""
    from mmlspark_tpu.core.exceptions import FriendlyError
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.serve.paging import PagedCachePool

    graph = build_model("transformer_lm", vocab_size=16, d_model=16,
                        heads=2, depth=1, max_len=8, attn_impl="dense")
    variables = jax.eval_shape(
        graph.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    with pytest.raises(FriendlyError, match="scalar memory"):
        PagedCachePool(graph, variables, 64, 32768, page_size=8)


# -- kanana-2-30b-a3b.report-backlog: the whole decode block and the widest
# prefill at the cell's own size, from the configuration's own file


def _kanana():
    import json
    from pathlib import Path

    from mmlspark_tpu.models import build_model

    cfg = json.loads((Path(__file__).resolve().parent.parent / "benchmark"
                      / "configs" / "kanana-2-30b-a3b.json").read_text())
    graph = build_model("hybrid_lm", **cfg["program"]["model"])
    variables = jax.eval_shape(
        graph.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, graph, variables


def test_kanana_decode_block_and_prefill_fit_one_v5e(chip, monkeypatch):
    """The cell's fused decode block (12 layers, 64 slots x 8,192 latent
    rows) and its 4,096-row prefill compile for the described v5e inside
    15.75 GB. The pool is ONE array a block, ``(64, 8192, 640)``
    bfloat16; the block's optimised HLO holds no ``copy`` or
    ``transpose`` of a pool-sized operand (the row is written and the
    rows are read where they lie), the pool is updated in place, the
    latent kernel stands under its name once a layer, and the
    temporaries stay under one block's rows."""
    from mmlspark_tpu.models.generate import (
        _cached_apply,
        init_cache,
        make_decode_block,
    )
    from mmlspark_tpu.ops.kv_cache import LatentRows
    from mmlspark_tpu.serve.cache_pool import SlotCachePool

    monkeypatch.setattr("mmlspark_tpu.core.env.is_tpu", lambda: True)
    cfg, graph, variables = _kanana()
    slots, rows = (cfg["program"]["engine"][k] for k in ("slots",
                                                          "cache_len"))
    pool = SlotCachePool(graph, variables, 1, rows)
    buffers = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((slots,) + a.shape[1:], a.dtype),
        pool.buffers)
    layers = len(cfg["program"]["model"]["attention"])
    assert len(buffers) == layers == 12
    for entry in buffers.values():
        assert isinstance(entry, LatentRows)
        assert entry.rows.shape == (slots, rows, 640)
    one = slots * rows * 640 * 2
    limit = int(15.75 * 2 ** 30)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    block = make_decode_block(graph)
    compiled = jax.jit(
        lambda v, b, pos, lv, tok, rem, eos: block(
            v, b, pos, lv, tok, rem, eos, 4),
        donate_argnums=(1, 2, 3),
    ).lower(*_on(chip, [variables, buffers, ints, live, ints, ints, ints])
            ).compile()
    text = compiled.as_text()
    assert not _pool_copies(text, (slots, rows, 640))
    assert not _pool_copies(text, (slots, 1, rows, 640))
    assert len(set(re.findall(r"%(attn_mla_decode\.\d+) = ", text))) == layers
    # every expert product reads its matrices where they lie, in HBM: XLA
    # stages none of them in VMEM ahead of the call (a 48 MiB operand it
    # staged before the kernel stated its scope: ``_compiler_params``)
    products = re.findall(r"%moe_(?:gate|up|down)\.\d+ = \S+ custom-call\("
                          r"([^)]*)\)", text)
    assert len(products) == 3 * (layers - 1)
    assert all(args.split(", ")[-1].startswith("%get-tuple-element")
               for args in products)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= layers * one
    assert memory.temp_size_in_bytes < one
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < limit

    def prefill(v, prompt):
        cache = init_cache(graph, v, 1, prompt.shape[1])
        return _cached_apply(graph, v, prompt, cache, 0)

    compiled = jax.jit(prefill).lower(*_on(chip, [
        variables, jax.ShapeDtypeStruct((1, 4096), jnp.int32)])).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    # the prefill runs beside the pool, which it does not hold
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes + layers * one) < limit


# -- lfm2-8b-a1b.synth-backlog: the whole decode block, the widest prefill
# and the pool's write at the cell's own size, from the configuration's file


def _lfm2():
    import json
    from pathlib import Path

    from mmlspark_tpu.models import build_model

    cfg = json.loads((Path(__file__).resolve().parent.parent / "benchmark"
                      / "configs" / "lfm2-8b-a1b.json").read_text())
    graph = build_model("hybrid_lm", **cfg["program"]["model"])
    variables = jax.eval_shape(
        graph.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, graph, variables


def test_lfm2_decode_block_prefill_and_pool_write_fit_one_v5e(
        chip, monkeypatch, capsys):
    """The cell's fused decode block (12 layers: 9 convolution states of
    ``(128, 4096)`` and 3 K/V pairs of ``(128, 4, 4096, 128)``, two heads
    of 64 a row), its 1,024-row prefill and the pool's write compile for
    the described v5e inside 15.75 GB. The block's optimised HLO holds no
    ``copy`` or ``transpose`` of a pool-sized operand, every pool entry is
    updated in place (the state by ``conv_decode``'s own alias), each
    kernel stands under its name once a layer, and the temporaries stay
    under one layer's K/V."""
    from mmlspark_tpu.models.generate import (
        _cached_apply,
        init_cache,
        make_decode_block,
    )
    from mmlspark_tpu.ops.kv_cache import HeadMajorKV, SlotState
    from mmlspark_tpu.serve.cache_pool import SlotCachePool

    monkeypatch.setattr("mmlspark_tpu.core.env.is_tpu", lambda: True)
    cfg, graph, variables = _lfm2()
    slots, rows = (cfg["program"]["engine"][k] for k in ("slots",
                                                          "cache_len"))
    pool = SlotCachePool(graph, variables, 1, rows)
    buffers = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((slots,) + a.shape[1:], a.dtype),
        pool.buffers)
    kinds = cfg["program"]["model"]["attention"]
    assert len(buffers) == len(kinds) == 12
    for i, kind in enumerate(kinds):
        entry = buffers[f"block{i}"]
        if kind == "conv":
            assert isinstance(entry, SlotState)
            assert entry.rows.shape == (slots, 2 * 2048)
        else:
            assert isinstance(entry, HeadMajorKV)
            assert entry.k.shape == entry.v.shape == (slots, 4, rows, 128)
    one = slots * 4 * rows * 128 * 2
    state = slots * 4096 * 2
    pooled = 6 * one + 9 * state
    limit = int(15.75 * 2 ** 30)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    block = make_decode_block(graph)
    compiled = jax.jit(
        lambda v, b, pos, lv, tok, rem, eos: block(
            v, b, pos, lv, tok, rem, eos, 4),
        donate_argnums=(1, 2, 3),
    ).lower(*_on(chip, [variables, buffers, ints, live, ints, ints, ints])
            ).compile()
    text = compiled.as_text()
    assert not _pool_copies(text, (slots, 4, rows, 128))
    assert len(set(re.findall(r"%(conv_decode\.\d+) = ", text))) == 9
    assert len(set(re.findall(r"%(attn_full_decode\.\d+) = ", text))) == 3
    memory = compiled.memory_analysis()
    print("lfm2 decode block: arguments", memory.argument_size_in_bytes,
          "aliased", memory.alias_size_in_bytes, "temporaries",
          memory.temp_size_in_bytes)
    assert memory.alias_size_in_bytes >= pooled
    assert memory.temp_size_in_bytes < 2 * one
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < limit

    def prefill(v, prompt):
        cache = init_cache(graph, v, 1, prompt.shape[1])
        return _cached_apply(graph, v, prompt, cache, 0)

    compiled = jax.jit(prefill).lower(*_on(chip, [
        variables, jax.ShapeDtypeStruct((1, 1024), jnp.int32)])).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "conv_prefill" in compiled.as_text()
    memory = compiled.memory_analysis()
    print("lfm2 prefill 1024: arguments", memory.argument_size_in_bytes,
          "temporaries", memory.temp_size_in_bytes, "outputs",
          memory.output_size_in_bytes)
    # the prefill runs beside the pool, which it does not hold
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes + pooled) < limit

    # the pool's one write: every entry aliased, no second pool
    cache = jax.eval_shape(lambda: init_cache(graph, variables, 1, 1024))
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = pool._write.lower(*_on(chip, (
        buffers, ints, live, cache, scalar, scalar, scalar))).compile()
    memory = compiled.memory_analysis()
    print("lfm2 pool write: aliased", memory.alias_size_in_bytes,
          "temporaries", memory.temp_size_in_bytes)
    assert memory.alias_size_in_bytes >= pooled
    assert memory.temp_size_in_bytes < one
    assert not _pool_copies(compiled.as_text(), (slots, 4, rows, 128))
    with capsys.disabled():
        print(capsys.readouterr().out, end="")
