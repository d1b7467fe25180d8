"""Operations and bytes the ALGORITHM needs, from shapes, for the
``sdar_moe`` family: layers of one kind (grouped-query attention, every
layer routed over ``sz["experts"]`` experts, all held here) that GENERATE
BY DIFFUSION OVER BLOCKS of ``L = sz["block_len"]``: a micro-step runs
``L`` rows a live slot, each reading its slot's clean prefix and the
block's own rows, ``live_lens`` giving per live slot the rows that
micro-step reads (prefix + ``L``; ``readers/block_steps.py`` works them
out). Of every ``S + 1`` micro-steps of a block, ``S`` denoise and need
the head's logits; the clean close needs none, so a micro-step's count
takes the head at ``S / (S + 1)`` of its work, the mean over a block.

Every count is of useful work at TRUE lengths and STORED widths; the
expert layer counts the PROGRAM'S COUNTERS (pairs and HIT experts a
routed layer and micro-step) where a ``spec`` carries them
(``counts/mimo_v2_flash.py``). A prompt's prefill counts its whole
blocks (what admission runs) and the mask's LIVE area: a row sees the
rows of its own block and of every block before it. No jax.
"""

from __future__ import annotations

from typing import Iterable

from benchmark.counts.mimo_v2_flash import (  # noqa: F401  (readers' names)
    ACT_BYTES,
    expert_params,
    moe_decode_bytes,
    moe_decode_flops,
    routed_layers,
    routing,
)


def sizes(cfg: dict, sz: dict) -> dict:
    """The reference's sizes with the bytes a parameter and a cached
    number are stored in (``program.stored``; bfloat16 where absent)."""
    stored = cfg.get("program", {}).get("stored") or {}
    return dict(sz, param_bytes=int(stored.get("param_bytes", 2)),
                kv_bytes=int(stored.get("kv_bytes", 2)))


def _head_share(sz: dict) -> float:
    """The share of a block's micro-steps that compute logits."""
    return sz["denoise_steps"] / (sz["denoise_steps"] + 1)


# -- the attention kernels -------------------------------------------------------


def attn_decode_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer's ``attn_block_decode`` in one micro-step: each live
    slot's ``L`` rows of query heads against the rows it reads, then the
    weighted values."""
    rows = sum(live_lens)
    return 2.0 * sz["heads"] * 2 * sz["dk"] * sz["block_len"] * rows


def attn_decode_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer's kernel: the K and V rows each live slot reads, once
    for all its ``L`` rows and all query heads of a KV head, and the
    queries in and the outputs out."""
    lens = list(live_lens)
    return (sum(lens) * 2 * sz["kvd"] * sz["kv_bytes"]
            + len(lens) * sz["block_len"] * 2 * sz["qd"] * ACT_BYTES)


def _live_area(sz: dict, prompt_len: int) -> tuple[int, int]:
    """The rows a prefill runs (the prompt's whole blocks) and the
    (query, key) pairs the block-causal mask lets through."""
    length = sz["block_len"]
    blocks = int(prompt_len) // length
    return blocks * length, length * length * blocks * (blocks + 1) // 2


def attn_prefill_flops(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """One layer's block-causal attention over a prompt's whole blocks:
    the mask's live area."""
    return 2.0 * sz["heads"] * 2 * sz["dk"] * _live_area(sz, prompt_len)[1]


def attn_prefill_bytes(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """One layer: Q, K and V of the whole blocks read, the output
    written."""
    rows = _live_area(sz, prompt_len)[0]
    return rows * (2 * sz["qd"] + 2 * sz["kvd"]) * ACT_BYTES


# -- a whole micro-step ------------------------------------------------------------


def _shared_params(sz: dict) -> int:
    """Matrices every row goes through in every micro-step: q, k, v and
    the output, and the router, of every layer."""
    d = sz["d"]
    return sz["layers"] * (2 * d * sz["qd"] + 2 * d * sz["kvd"]
                           + d * sz["experts"])


def _small_params(sz: dict) -> int:
    """Gains and the heads' norms."""
    return 2 * sz["layers"] * (sz["d"] + sz["dk"]) + sz["d"]


def decode_step_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One micro-step, the mean over a block's: every live slot's ``L``
    rows through the shared matrices, the head's share, every pair
    through its expert, every layer's attention kernel."""
    lens = list(live_lens)
    rows = len(lens) * sz["block_len"]
    pairs, hit = routing(sz, rows, spec)
    return (2.0 * rows * (_shared_params(sz)
                          + _head_share(sz) * sz["d"] * sz["v"])
            + routed_layers(sz) * moe_decode_flops(sz, pairs, hit)
            + sz["layers"] * attn_decode_flops(sz, lens))


def decode_step_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One micro-step's least traffic, the mean over a block's: every
    shared parameter and every expert HIT once at its stored width, the
    head's share, each live slot's rows read once a layer and its block's
    ``L`` rows written."""
    lens = list(live_lens)
    rows = len(lens) * sz["block_len"]
    pairs, hit = routing(sz, rows, spec)
    params = ((_shared_params(sz) + _small_params(sz)
               + _head_share(sz) * sz["d"] * sz["v"]) * sz["param_bytes"]
              + routed_layers(sz) * hit * expert_params(sz)
              * sz["param_bytes"])
    kv = sz["layers"] * (sum(lens) + rows) * 2 * sz["kvd"] * sz["kv_bytes"]
    return params + kv
