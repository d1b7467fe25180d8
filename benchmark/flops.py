"""Operations and bytes the ALGORITHM needs, from shapes: the yardstick's
arithmetic, kept where no later PR can change it.

Every count is of useful work at TRUE lengths and STORED widths: a prompt
of 640 tokens counts 640 positions, not its bucket of 1,024; a decode
micro-step counts the slots that are live and the cache rows they really
hold, not the pool's 24 x 1,024; parameters count at the width they are
stored in (float32 today: 4 bytes). A share of a peak built on these can
only be pushed over 100% by a timing that leaves work out, never by the
count.

``sz`` is :func:`benchmark.reference.sizes` of a configuration. No jax.
"""

from __future__ import annotations

from typing import Iterable

KV_BYTES = 2      # the dense pool stores K and V in bfloat16
PARAM_BYTES = 4   # parameters are stored in float32


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
    return PARAM_BYTES * (matmul_params(sz) + small)


# -- serving -----------------------------------------------------------------


def attn_decode_flops(sz: dict, live_lens: Iterable[int]) -> int:
    """One layer's attention for one micro-step: each live row's single
    query against its ``len`` cached keys, then the weighted values."""
    return sum(4 * int(n) * sz["d"] for n in live_lens)


def attn_decode_bytes(sz: dict, live_lens: Iterable[int]) -> int:
    """One layer: the K and V rows each live slot holds, read once, plus
    the queries in and the outputs out (bfloat16)."""
    lens = [int(n) for n in live_lens]
    rows = sum(lens)
    return 2 * rows * sz["d"] * KV_BYTES + 2 * len(lens) * sz["d"] * 2


def decode_step_flops(sz: dict, live_lens: Iterable[int]) -> int:
    """One decode micro-step: every live slot's token through all the
    matrices, and its attention over the rows it holds."""
    lens = [int(n) for n in live_lens]
    return (2 * len(lens) * matmul_params(sz)
            + sz["layers"] * attn_decode_flops(sz, lens))


def decode_step_bytes(sz: dict, live_lens: Iterable[int]) -> int:
    """One decode micro-step's least traffic: every parameter once at its
    stored width, the live K and V rows once, the new rows written."""
    lens = [int(n) for n in live_lens]
    kv_read = 2 * sum(lens) * sz["d"] * KV_BYTES * sz["layers"]
    kv_write = 2 * len(lens) * sz["d"] * KV_BYTES * sz["layers"]
    return stored_param_bytes(sz) + kv_read + kv_write


def attn_prefill_flops(sz: dict, prompt_len: int) -> int:
    """One layer's causal attention over a prompt of its TRUE length:
    position ``i`` sees ``i + 1`` keys, scores and weighted values."""
    p = int(prompt_len)
    return 4 * sz["d"] * p * (p + 1) // 2


def attn_prefill_bytes(sz: dict, prompt_len: int) -> int:
    """One layer: Q, K and V read and the output written, bfloat16."""
    return 4 * int(prompt_len) * sz["d"] * 2


def prefill_flops(sz: dict, prompt_len: int) -> int:
    """A prefill of the true prompt length: every position through the
    blocks, causal attention, and the head for the LAST position only
    (the one logit row the first token needs)."""
    p = int(prompt_len)
    return (2 * p * sz["layers"] * layer_matmul_params(sz)
            + sz["layers"] * attn_prefill_flops(sz, p)
            + 2 * sz["d"] * sz["v"])


# -- training ----------------------------------------------------------------


def attn_train_flops(sz: dict, seq: int) -> int:
    """One layer, one sequence, forward AND backward: the backward pass
    needs twice the forward's products. The flash backward's recomputed
    scores are not counted."""
    return 3 * attn_prefill_flops(sz, seq)


def attn_train_bytes(sz: dict, seq: int) -> int:
    """One layer, one sequence: forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV (bfloat16)."""
    return (4 + 8) * int(seq) * sz["d"] * 2


def train_flops_per_token(sz: dict, seq: int) -> float:
    """Model FLOPs of one token in one optimizer step: 6 x the weights
    that multiply it (forward 2, backward 4) plus its share of the causal
    attention. Recomputation is not counted."""
    return (6 * matmul_params(sz)
            + sz["layers"] * attn_train_flops(sz, seq) / int(seq))


# -- shares of the peaks -----------------------------------------------------


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple[float, str] | None:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) as a percentage of
    ``seconds``, and which side bounds it. None when there is no time."""
    if not seconds or seconds <= 0:
        return None
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    side = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, side
