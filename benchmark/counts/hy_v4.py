"""Operations and bytes the ALGORITHM needs, from shapes, for the ``hy_v4``
family: every layer's attention is sparse latent attention (a latent
cache of ONE row ``[c ; k_rope]`` a position for all heads, the values its
first ``rank`` columns), each query reading the ``min(topk, p + 1)`` rows
its layer's choice holds; a layer with its own lightning indexer scores
every live position against ONE index key a position first. The FFN is
dense or routed over ``sz["experts"]`` experts of which ``sz["held_n"]``
are held here, beside an always-on shared expert; the residual is
``hc`` streams, mixed around every sublayer.

Every count is of useful work at TRUE lengths:

- ``index`` (a ``full`` layer's scores, the kernel ``index_decode`` or
  ``index_prefill``): every live key against each of ``hi`` query heads of
  ``di``, a ReLU and the head-weighted sum, ``hi (2 di + 2)`` operations
  a key; the keys read once (a prefill's once for each block of queries
  the kernel takes), the queries in and the scores of live keys out.
- ``dsa`` (the read, ``attn_dsa_decode`` or ``attn_dsa_prefill``): each
  chosen row ONCE at its own 576 numbers, ``heads x (576 + 512)``
  multiply-adds a row: a decode step's slot reads its rows once for all
  heads, and so does each query of a prefill (no two queries share a
  choice). A call's rows lie in VMEM, where XLA's gather put them (a
  decode step's 16 slots, a prefill's 16 queries: 2,048 rows of 640
  lanes each, 42 MB), with its queries and its output, so the kernel
  moves no HBM bytes (:func:`attn_decode_bytes`,
  :func:`attn_prefill_bytes`).

A decode kernel runs once a layer a micro-step over every slot: its count
is one such call. A prefill's kernels run in blocks of queries (the
program's ``READ_QUERY_CHUNK`` and ``INDEX_QUERY_CHUNK``, over chunks of
``prefill_chunk`` tokens, the last padded to its power of two): a count
of a prompt is its work over the calls its layer makes, the mean work of
a call, which is what the reader divides by a call's mean time. The
expert layer's counts are the ``mimo_v2_flash`` family's (pairs and HIT
experts from the program's counters). No jax.
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

#: queries a prefill's calls of the read and of the score kernel take
#: (``mmlspark_tpu/ops/sparse_attention.py``), where a chunk splits evenly
READ_QUERY_CHUNK = 16
INDEX_QUERY_CHUNK = 512
SCORE_BYTES = 4   # an index score: float32


def sizes(cfg: dict, sz: dict) -> dict:
    """The reference's sizes with the bytes a parameter, a cached number
    and the head's parameter are stored in (``program.stored``), and the
    engine's prefill chunk."""
    program = cfg.get("program", {})
    stored = program.get("stored") or {}
    return dict(sz, param_bytes=int(stored.get("param_bytes", 2)),
                kv_bytes=int(stored.get("kv_bytes", 2)),
                head_bytes=int(stored.get("head_bytes", 4)),
                prefill_chunk=int((program.get("engine") or {}).get(
                    "prefill_chunk") or 0))


def own_layers(sz: dict) -> int:
    return sum(1 for own in sz["own"] if own)


def attn_params(sz: dict) -> int:
    """One layer's attention and mixing matrices, without the indexer:
    the query's two, the joint down-projection, the per-head
    up-projection, the gate, the output, and both sublayers' mixing
    maps."""
    d = sz["d"]
    return (d * sz["qr"] + sz["qr"] * sz["qd"] + d * sz["ad"]
            + sz["rank"] * sz["bd"] + 2 * d * sz["od"]
            + 2 * sz["nd"] * sz["hw"])


def index_params(sz: dict) -> int:
    """A full layer's indexer: queries from the query's latent, the key
    and the head weights."""
    return sz["qr"] * sz["iqd"] + sz["d"] * (sz["di"] + sz["hi"])


def _kind(spec: dict | None) -> str:
    return (spec or {}).get("kind", "dsa")


def _read(sz: dict, n: int) -> int:
    return min(int(n), sz["topk"])


# -- attention: one call of a kernel -------------------------------------------


def attn_decode_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer's kernel for one micro-step over the slots' live
    lengths: the index scores (``kind`` ``index``) or the read (``dsa``)."""
    lens = list(live_lens)
    if _kind(spec) == "index":
        return sz["hi"] * (2.0 * sz["di"] + 2.0) * sum(lens)
    return 2.0 * sz["heads"] * (sz["ad"] + sz["rank"]) * sum(
        _read(sz, n) for n in lens)


def attn_decode_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer's HBM traffic: the live index keys once, the queries in
    and the live scores out (``index``). The read (``dsa``) moves none:
    XLA's gather writes every slot's chosen rows straight into VMEM, and
    the queries and the result lie there too (``S(1)`` in the compiled
    decode block of the cell: 16 slots x 2,048 rows of 640 lanes, 42 MB),
    so the kernel is bound by its operations; the rows' read from HBM is
    the gather's, an XLA fusion, and :func:`decode_step_bytes` counts
    it."""
    lens = list(live_lens)
    if _kind(spec) == "index":
        return (sum(lens) * (sz["di"] * sz["kv_bytes"] + SCORE_BYTES)
                + len(lens) * sz["iqd"] * ACT_BYTES)
    return 0.0


def _chunks(sz: dict, n: int) -> list:
    """The widths of the chunks a prompt of ``n`` is prefilled in: whole
    chunks, then the rest padded to its power of two (at least 8)."""
    chunk, widths, left = sz["prefill_chunk"] or n, [], int(n)
    while left > chunk:
        widths.append(chunk)
        left -= chunk
    last = 8
    while last < left:
        last *= 2
    return widths + [min(last, chunk)]


def _calls(sz: dict, n: int, block: int) -> int:
    return sum(w // block if w > block and w % block == 0 else 1
               for w in _chunks(sz, n))


def attn_prefill_flops(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """A prompt of its TRUE length, one layer, over the calls the kernel
    of ``kind`` makes for it."""
    n = int(prompt_len)
    if _kind(spec) == "index":
        work = sz["hi"] * (2.0 * sz["di"] + 2.0) * (n * (n + 1) // 2)
        return work / _calls(sz, n, INDEX_QUERY_CHUNK)
    rows = sum(_read(sz, p + 1) for p in range(n))
    return (2.0 * sz["heads"] * (sz["ad"] + sz["rank"]) * rows
            / _calls(sz, n, READ_QUERY_CHUNK))


def attn_prefill_bytes(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """A prompt, one layer, over the kernel's calls: the keys once a block
    of queries, the queries in and the live scores out (``index``). The
    read (``dsa``) moves none: a call's 16 queries, their chosen rows and
    their outputs lie in VMEM (``S(1)`` in the compiled chunk of the
    cell), as a decode step's do (:func:`attn_decode_bytes`)."""
    n = int(prompt_len)
    if _kind(spec) == "index":
        ends = range(INDEX_QUERY_CHUNK, n + INDEX_QUERY_CHUNK,
                     INDEX_QUERY_CHUNK)
        work = (sum(min(e, n) for e in ends) * sz["di"] * sz["kv_bytes"]
                + (n * (n + 1) // 2) * SCORE_BYTES
                + n * sz["iqd"] * ACT_BYTES)
        return work / _calls(sz, n, INDEX_QUERY_CHUNK)
    return 0.0


# -- a whole decode micro-step -------------------------------------------------


def _dense_params(sz: dict) -> int:
    """Matrices every token goes through at the parameters' stored width:
    attention and mixing of every layer, the indexers, the dense FFNs, the
    routers and the shared experts (the head is counted apart)."""
    d = sz["d"]
    return (sz["layers"] * attn_params(sz) + own_layers(sz) * index_params(sz)
            + sum(3 * d * sz["f"] for f in sz["ffns"] if f == "dense")
            + routed_layers(sz) * (d * sz["experts"] + 3 * d * sz["sf"]))


def _small_params(sz: dict) -> int:
    """Gains, sinks, biases and the selection biases."""
    return (sz["layers"] * (2 * sz["d"] + sz["rank"] + sz["qr"]
                            + sz["heads"] + 2 * (sz["hw"] + 3))
            + own_layers(sz) * 2 * sz["di"] + sz["d"]
            + routed_layers(sz) * sz["experts"])


def decode_step_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One decode micro-step: every live token through the matrices all
    tokens share and the head, its pairs through their experts, every
    full layer's index scores and every layer's read."""
    lens = list(live_lens)
    pairs, hit = routing(sz, len(lens), spec)
    return (2.0 * len(lens) * (_dense_params(sz) + sz["d"] * sz["v"])
            + routed_layers(sz) * moe_decode_flops(sz, pairs, hit)
            + own_layers(sz) * attn_decode_flops(sz, lens, {"kind": "index"})
            + sz["layers"] * attn_decode_flops(sz, lens, {"kind": "dsa"}))


def decode_step_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One decode micro-step's least traffic: every shared parameter and
    every expert that was HIT once at its stored width, the float32 head,
    every full layer's live index keys and every layer's chosen rows
    once, and the new rows and keys written."""
    lens = list(live_lens)
    pairs, hit = routing(sz, len(lens), spec)
    params = ((_dense_params(sz) + _small_params(sz)) * sz["param_bytes"]
              + sz["d"] * sz["v"] * sz["head_bytes"]
              + routed_layers(sz) * hit * expert_params(sz)
              * sz["param_bytes"])
    keys = own_layers(sz) * (sum(lens) + len(lens)) * sz["di"]
    rows = sz["layers"] * (sum(_read(sz, n) for n in lens)
                           + len(lens)) * sz["ad"]
    return params + (keys + rows) * sz["kv_bytes"]
