"""Operations and bytes the ALGORITHM needs, from shapes, for the ``gpt2``
family (what a configuration with no ``program.reference`` means): the
yardstick's arithmetic, kept where no later PR can change it.

Every count is of useful work at TRUE lengths and STORED widths: a prompt
of 640 tokens counts 640 positions, not its bucket of 1,024; a decode
micro-step counts the slots that are live and the cache rows they really
hold, not the pool's 24 x 1,024; parameters and cache rows count at the
width they are stored in. A share of a peak built on these can only be
pushed over 100% by a timing that leaves work out, never by the count.

``sz`` is :func:`sizes` of a configuration: the reference's sizes with the
stored widths added. Every function a reader calls takes the metric
file's ``spec`` last, so that a family with layers of several kinds can
count the kind a metric's file names; this family has one kind and
ignores it. No jax.
"""

from __future__ import annotations

from typing import Iterable

KV_BYTES = 2      # the dense pool stores K and V in bfloat16
PARAM_BYTES = 4   # parameters are stored in float32


def sizes(cfg: dict, sz: dict) -> dict:
    """The reference's sizes with the widths things are stored in, read
    from the configuration's ``program.stored`` where it states them
    (``param_bytes``, ``kv_bytes``); float32 parameters and a bfloat16
    pool where it does not, as ``models/transformer.py`` and the dense
    pool store them."""
    stored = cfg.get("program", {}).get("stored") or {}
    return dict(sz, param_bytes=int(stored.get("param_bytes", PARAM_BYTES)),
                kv_bytes=int(stored.get("kv_bytes", KV_BYTES)))


def layer_matmul_params(sz: dict) -> int:
    """Weights of one block that multiply a token: QKV, attention output,
    and the two MLP matrices."""
    d, f = sz["d"], sz["f"]
    return d * 3 * d + d * d + d * f + f * d


def matmul_params(sz: dict) -> int:
    """All weights that multiply a token: the blocks and the output head.
    Embedding tables are looked up, not multiplied."""
    return sz["layers"] * layer_matmul_params(sz) + sz["d"] * sz["v"]


def stored_param_bytes(sz: dict) -> int:
    """Bytes of every parameter a forward pass over one token must read:
    the matrices, their biases and the LayerNorms. The embedding tables
    are read one row per token and are left out."""
    d, f, v, n = sz["d"], sz["f"], sz["v"], sz["layers"]
    small = n * (3 * d + d + f + d + 4 * d) + 2 * d + v
    return sz["param_bytes"] * (matmul_params(sz) + small)


# -- serving -----------------------------------------------------------------


def attn_decode_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> int:
    """One layer's attention for one micro-step: each live row's single
    query against its ``len`` cached keys, then the weighted values."""
    return sum(4 * int(n) * sz["d"] for n in live_lens)


def attn_decode_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> int:
    """One layer: the K and V rows each live slot holds, read once, plus
    the queries in and the outputs out (bfloat16)."""
    lens = [int(n) for n in live_lens]
    rows = sum(lens)
    return 2 * rows * sz["d"] * sz["kv_bytes"] + 2 * len(lens) * sz["d"] * 2


def decode_step_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> int:
    """One decode micro-step: every live slot's token through all the
    matrices, and its attention over the rows it holds."""
    lens = [int(n) for n in live_lens]
    return (2 * len(lens) * matmul_params(sz)
            + sz["layers"] * attn_decode_flops(sz, lens))


def decode_step_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> int:
    """One decode micro-step's least traffic: every parameter once at its
    stored width, the live K and V rows once, the new rows written."""
    lens = [int(n) for n in live_lens]
    kv_read = 2 * sum(lens) * sz["d"] * sz["kv_bytes"] * sz["layers"]
    kv_write = 2 * len(lens) * sz["d"] * sz["kv_bytes"] * sz["layers"]
    return stored_param_bytes(sz) + kv_read + kv_write


def attn_prefill_flops(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> int:
    """One layer's causal attention over a prompt of its TRUE length:
    position ``i`` sees ``i + 1`` keys, scores and weighted values."""
    p = int(prompt_len)
    return 4 * sz["d"] * p * (p + 1) // 2


def attn_prefill_bytes(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> int:
    """One layer: Q, K and V read and the output written, bfloat16."""
    return 4 * int(prompt_len) * sz["d"] * 2


def prefill_flops(sz: dict, prompt_len: int,
                  spec: dict | None = None) -> int:
    """A prefill of the true prompt length: every position through the
    blocks, causal attention, and the head for the LAST position only
    (the one logit row the first token needs)."""
    p = int(prompt_len)
    return (2 * p * sz["layers"] * layer_matmul_params(sz)
            + sz["layers"] * attn_prefill_flops(sz, p)
            + 2 * sz["d"] * sz["v"])


# -- training ----------------------------------------------------------------


def attn_train_flops(sz: dict, seq: int,
                     spec: dict | None = None) -> int:
    """One layer, one sequence, forward AND backward: the backward pass
    needs twice the forward's products. The flash backward's recomputed
    scores are not counted."""
    return 3 * attn_prefill_flops(sz, seq)


def attn_train_bytes(sz: dict, seq: int,
                     spec: dict | None = None) -> int:
    """One layer, one sequence: forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV (bfloat16)."""
    return (4 + 8) * int(seq) * sz["d"] * 2


def train_flops_per_token(sz: dict, seq: int,
                          spec: dict | None = None) -> float:
    """Model FLOPs of one token in one optimizer step: 6 x the weights
    that multiply it (forward 2, backward 4) plus its share of the causal
    attention. Recomputation is not counted."""
    return (6 * matmul_params(sz)
            + sz["layers"] * attn_train_flops(sz, seq) / int(seq))
