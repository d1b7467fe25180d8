"""The counts of the ``gqa_rope`` family: as the ``gpt2`` family's, but the
fused projection has ``heads + 2 x kv_heads`` heads of columns and a cache
row of K or V is ``kv_heads x head`` wide (``sz["kv_d"]``), not ``d``. It
brings the counts a serving cell's readers ask for, and no training ones.
No jax."""

from __future__ import annotations


def sizes(cfg: dict, sz: dict) -> dict:
    stored = cfg["program"].get("stored") or {}
    return dict(sz, param_bytes=int(stored.get("param_bytes", 4)),
                kv_bytes=int(stored.get("kv_bytes", 2)))


def matmul_params(sz: dict) -> int:
    d, f = sz["d"], sz["f"]
    return sz["layers"] * (d * sz["qkv"] + d * d + 2 * d * f) + d * sz["v"]


def stored_param_bytes(sz: dict) -> int:
    d, f = sz["d"], sz["f"]
    small = sz["layers"] * (sz["qkv"] + d + f + d + 4 * d) + 2 * d + sz["v"]
    return sz["param_bytes"] * (matmul_params(sz) + small)


def attn_decode_flops(sz: dict, live_lens, spec=None) -> int:
    """One layer, one micro-step: every query head against its group's
    keys, then the weighted values."""
    return sum(4 * int(n) * sz["d"] for n in live_lens)


def attn_decode_bytes(sz: dict, live_lens, spec=None) -> int:
    """One layer: the K and V rows each live slot holds, ``kv_d`` wide,
    read once, plus the queries in and the outputs out (bfloat16)."""
    lens = [int(n) for n in live_lens]
    return (2 * sum(lens) * sz["kv_d"] * sz["kv_bytes"]
            + 2 * len(lens) * sz["d"] * 2)


def decode_step_flops(sz: dict, live_lens, spec=None) -> int:
    lens = [int(n) for n in live_lens]
    return (2 * len(lens) * matmul_params(sz)
            + sz["layers"] * attn_decode_flops(sz, lens))


def decode_step_bytes(sz: dict, live_lens, spec=None) -> int:
    lens = [int(n) for n in live_lens]
    rows = 2 * (sum(lens) + len(lens)) * sz["kv_d"] * sz["kv_bytes"]
    return stored_param_bytes(sz) + sz["layers"] * rows
