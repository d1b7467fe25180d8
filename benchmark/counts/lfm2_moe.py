"""Operations and bytes the ALGORITHM needs, from shapes, for the
``lfm2_moe`` family: layers of two operator kinds (``conv``: a gated short
convolution whose state is ``K - 1`` rows a slot, whatever the slot's
length; ``full``: grouped-query attention over every position) and two FFN
kinds (dense SwiGLU; routed over ``sz["experts"]`` experts, all held
here).

Every count is of useful work at TRUE lengths and STORED widths. A
convolution layer's decode KERNEL (``conv_decode``) does, for each LIVE
slot, two gates and ``K`` multiply-adds a channel, and of its bytes only
the filter's taps HAVE to come from the chip's memory: the projection's
row it reads is the product's output before it, the row it writes the
input of the product after it, and the state is a buffer carried from
micro-step to micro-step that a fused block need not send through HBM
(on a v5e it is not sent: the call takes 2.2 us for 4.2 MB touched, 1.9
TB/s, twice what HBM gives; my chip run, PR 35, ``PERF.md`` section 6). So
the kernel is bound by the latency of a call, not by a roofline, and its
share reads a percent or two by construction: it is there so that a
slower call shows. The layer's two projections are the STEP'S, not the
kernel's. The attention kernels' and the expert layer's counts are the
``mimo_v2_flash`` family's at this family's sizes (one attention kind,
keys and values equally wide; pairs and HIT experts from the program's
counters). ``spec["kind"]`` in (``conv``, ``full``) counts one layer of
that kind. No jax.
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


def _layers(sz: dict, kind: str) -> int:
    return sum(1 for k in sz["kinds"] if k == kind)


def operator_params(sz: dict, kind: str) -> int:
    """One layer's operator matrices: the convolution's two projections,
    or q, k, v and the output."""
    d = sz["d"]
    if kind == "conv":
        return d * 3 * d + d * d
    return 2 * d * sz["qd"] + 2 * d * sz["kvd"]


# -- the operators' kernels ----------------------------------------------------


def attn_decode_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer's kernel for one micro-step. ``full``: each live slot's
    query heads against its live rows, then the weighted values. ``conv``:
    each live slot's two gates and ``K`` multiply-adds a channel."""
    lens = list(live_lens)
    if (spec or {}).get("kind") == "conv":
        return len(lens) * sz["d"] * (2 + 2 * sz["K"])
    return 2.0 * sz["heads"] * 2 * sz["dk"] * sum(lens)


def attn_decode_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer's kernel. ``full``: the live K and V rows once (a KV
    head's rows serve its whole group), the queries in and the outputs
    out. ``conv``: the taps in float32; what the kernel reads and writes
    for a slot never has to cross HBM (the module's docstring)."""
    lens = list(live_lens)
    if (spec or {}).get("kind") == "conv":
        return sz["K"] * sz["d"] * 4
    return (sum(lens) * 2 * sz["kvd"] * sz["kv_bytes"]
            + len(lens) * 2 * sz["qd"] * ACT_BYTES)


def attn_prefill_flops(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """One ``full`` layer's causal attention over a prompt of its TRUE
    length."""
    p = int(prompt_len)
    return 2.0 * sz["heads"] * 2 * sz["dk"] * (p * (p + 1) // 2)


def attn_prefill_bytes(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """One ``full`` layer: Q, K and V read and the output written."""
    return int(prompt_len) * (2 * sz["qd"] + 2 * sz["kvd"]) * ACT_BYTES


# -- a whole decode micro-step -------------------------------------------------


def _dense_params(sz: dict) -> int:
    """Matrices every token goes through: every layer's operator, the
    dense FFNs, the routers and the head."""
    d = sz["d"]
    return (sum(operator_params(sz, kind) for kind in sz["kinds"])
            + sum(3 * d * sz["f"] for f in sz["ffns"] if f == "dense")
            + routed_layers(sz) * d * sz["experts"] + d * sz["v"])


def _small_params(sz: dict) -> int:
    """Gains, the heads' norms, the filters' taps and selection biases."""
    return (2 * sz["layers"] * sz["d"] + sz["d"]
            + _layers(sz, "full") * 2 * sz["dk"]
            + _layers(sz, "conv") * sz["K"] * sz["d"]
            + routed_layers(sz) * sz["experts"])


def decode_step_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One decode micro-step: every live token through the matrices all
    tokens share, its pairs through their experts, and every layer's
    operator kernel."""
    lens = list(live_lens)
    pairs, hit = routing(sz, len(lens), spec)
    return (2.0 * len(lens) * _dense_params(sz)
            + routed_layers(sz) * moe_decode_flops(sz, pairs, hit)
            + sum(attn_decode_flops(sz, lens, {"kind": kind})
                  for kind in sz["kinds"]))


def decode_step_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One decode micro-step's least traffic: every shared parameter and
    every expert that was HIT once at its stored width, the ``full``
    layers' live K and V rows once and their new rows written, and the
    ``conv`` layers' state of the live slots read and written (18.9 MB of
    some 9 GB at the cell's size: a micro-step that is its own block has
    to, and the share is the whole step's, not a kernel's)."""
    lens = list(live_lens)
    pairs, hit = routing(sz, len(lens), spec)
    params = ((_dense_params(sz) + _small_params(sz)) * sz["param_bytes"]
              + routed_layers(sz) * hit * expert_params(sz)
              * sz["param_bytes"])
    kv = (_layers(sz, "full") * (sum(lens) + len(lens)) * 2 * sz["kvd"]
          * sz["kv_bytes"])
    state = (_layers(sz, "conv") * len(lens) * 2 * (sz["K"] - 1) * sz["d"]
             * sz["kv_bytes"])
    return params + kv + state
