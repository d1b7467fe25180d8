"""Operations and bytes the ALGORITHM needs, from shapes, for the
``deepseek_v3`` family: every layer's attention is of the one kind ``mla``
(a latent cache: ONE row ``[c ; k_rope]`` a position for all heads, the
values its first ``rank`` columns), the FFN dense or routed over
``sz["experts"]`` experts of which ``sz["held_n"]`` are held here, beside
an always-on shared expert.

Every count is of useful work at TRUE lengths. A decode step's attention
KERNEL reads each live row ONCE, for its scores and its weighted sum both:
32 query heads x (576 + 512) multiply-adds a row, and the row's OWN 576
numbers in the cache's bytes. The pool holds a row in whole lanes
(``program.stored.latent_width``, 640: what ``hbm_peak_pct`` and the pool
write's ``bytes`` show), but the pad lanes are no part of what the
algorithm needs: a pool that stores rows unpadded reads less and must not
see its share of the roofline fall for it. The products that fold
``W_kvb`` into the
query and apply its value half after the sum are the STEP'S, not the
kernel's: they go through ``W_kvb`` once a token, as the expanded form
would. A prefill's kernel is causal attention over expanded heads (keys
192, values 128), a prompt at its own length. The expert layer's counts
are the ``mimo_v2_flash`` family's (pairs and HIT experts from the
program's counters). No jax.
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


def attn_params(sz: dict) -> int:
    """One layer's attention matrices: q, the joint down-projection, the
    per-head up-projection and the output."""
    d = sz["d"]
    return d * sz["qd"] + d * sz["ad"] + sz["rank"] * sz["bd"] + sz["od"] * d


# -- attention -----------------------------------------------------------------


def attn_decode_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer's decode kernel for one micro-step: every head's absorbed
    query against each live row, then the weighted sum of the rows'
    first ``rank`` columns."""
    return 2.0 * sz["heads"] * (sz["ad"] + sz["rank"]) * sum(live_lens)


def attn_decode_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer: each live row ONCE at its own width, the absorbed
    queries in and the latent outputs out."""
    lens = list(live_lens)
    return (sum(lens) * sz["ad"] * sz["kv_bytes"]
            + len(lens) * sz["heads"] * (sz["ad"] + sz["rank"]) * ACT_BYTES)


def attn_prefill_flops(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """One layer's causal attention over a prompt of its TRUE length,
    expanded: keys ``dn + dr`` wide, values ``dv``."""
    p = int(prompt_len)
    return (2.0 * sz["heads"] * (sz["dn"] + sz["dr"] + sz["dv"])
            * (p * (p + 1) // 2))


def attn_prefill_bytes(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """One layer: Q, the expanded K and V read and the output written."""
    return (int(prompt_len) * (2 * sz["qd"] + 2 * sz["od"]) * ACT_BYTES)


# -- a whole decode micro-step -------------------------------------------------


def _dense_params(sz: dict) -> int:
    """Matrices every token goes through: attention of every layer
    (``W_kvb`` through the absorbed products), the dense FFNs, the routers,
    the shared experts and the head."""
    d = sz["d"]
    return (sz["layers"] * attn_params(sz)
            + sum(3 * d * sz["f"] for f in sz["ffns"] if f == "dense")
            + routed_layers(sz) * (d * sz["experts"] + 3 * d * sz["sf"])
            + d * sz["v"])


def _small_params(sz: dict) -> int:
    """Gains and selection biases."""
    return (sz["layers"] * (2 * sz["d"] + sz["rank"]) + sz["d"]
            + routed_layers(sz) * sz["experts"])


def decode_step_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One decode micro-step: every live token through the matrices all
    tokens share, its pairs through their experts, and every layer's
    attention over the live rows."""
    lens = list(live_lens)
    pairs, hit = routing(sz, len(lens), spec)
    return (2.0 * len(lens) * _dense_params(sz)
            + routed_layers(sz) * moe_decode_flops(sz, pairs, hit)
            + sz["layers"] * attn_decode_flops(sz, lens))


def decode_step_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One decode micro-step's least traffic: every shared parameter and
    every expert that was HIT once at its stored width, every layer's
    live rows once, and the new rows written."""
    lens = list(live_lens)
    pairs, hit = routing(sz, len(lens), spec)
    params = ((_dense_params(sz) + _small_params(sz)) * sz["param_bytes"]
              + routed_layers(sz) * hit * expert_params(sz)
              * sz["param_bytes"])
    rows = (sum(lens) + len(lens)) * sz["ad"] * sz["kv_bytes"]
    return params + sz["layers"] * rows
