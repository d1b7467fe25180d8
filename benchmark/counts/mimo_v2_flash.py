"""Operations and bytes the ALGORITHM needs, from shapes, for the
``mimo_v2_flash`` family: layers of two attention kinds (``full``: every
position; ``swa``: a window of ``sz["window"]`` positions and a sink) and
two FFN kinds (dense SwiGLU; routed over ``sz["experts"]`` experts of
which ``sz["held_n"]`` are held here).

Every count is of useful work at TRUE lengths and STORED widths: a window
layer's rows count ``min(len, window)``, never the pool's; a prompt counts
its own length, never its bucket; the expert layer counts the (token,
expert) pairs that fell on held experts and the held experts that were
hit, never all that are held. Those two are the PROGRAM'S COUNTERS
(``expert_pairs`` and ``experts_hit`` of the engine's ``dispatch`` event,
per routed layer and micro-step): a reader that has them hands them over
in the metric's ``spec``; where a spec has none, the count takes what
routing spreads evenly would give for the live tokens.

``spec["kind"]`` in (``full``, ``swa``) counts one layer of that kind;
absent, an attention count is the mean over the layers held. No jax.
"""

from __future__ import annotations

from typing import Iterable

ACT_BYTES = 2   # queries, outputs and the tokens an expert reads: bfloat16


def sizes(cfg: dict, sz: dict) -> dict:
    """The reference's sizes with the widths parameters and cache rows are
    stored in (``program.stored``; bfloat16 both where absent)."""
    stored = cfg.get("program", {}).get("stored") or {}
    return dict(sz, param_bytes=int(stored.get("param_bytes", 2)),
                kv_bytes=int(stored.get("kv_bytes", 2)))


def _kinds(sz: dict, spec: dict | None) -> tuple:
    kind = (spec or {}).get("kind")
    return (kind,) if kind else tuple(sz["kinds"])


def _rows(sz: dict, kind: str, length: int) -> int:
    """Cache rows a query at the end of ``length`` positions attends."""
    return min(int(length), sz["window"]) if kind == "swa" else int(length)


def attn_params(sz: dict, kind: str) -> int:
    """One layer's attention matrices: q, k, v and the output."""
    d, hk = sz["d"], sz["hk"][kind]
    return (d * sz["qd"] + d * hk * sz["dk"] + d * hk * sz["dv"]
            + sz["od"] * d)


def expert_params(sz: dict) -> int:
    return 3 * sz["d"] * sz["ef"]


def routed_layers(sz: dict) -> int:
    return sum(1 for f in sz["ffns"] if f == "routed")


def routing(sz: dict, live: int, spec: dict | None = None) -> tuple:
    """``(pairs, hit)`` of one routed layer in one micro-step of ``live``
    tokens: the program's counters where ``spec`` carries them, else what
    even routing gives (each token's ``top_k`` of ``experts`` land on a
    held one with probability ``held_n / experts``)."""
    spec = spec or {}
    if "expert_pairs" in spec and "experts_hit" in spec:
        return float(spec["expert_pairs"]), float(spec["experts_hit"])
    share = sz["top_k"] / sz["experts"]
    return (live * sz["held_n"] * share,
            sz["held_n"] * (1.0 - (1.0 - share) ** live))


# -- the expert layer ----------------------------------------------------------


def moe_decode_flops(sz: dict, pairs: float, hit: float,
                     spec: dict | None = None) -> float:
    """One routed layer's experts in one micro-step: every pair through
    its expert's three matrices."""
    return 2.0 * pairs * expert_params(sz)


def moe_decode_bytes(sz: dict, pairs: float, hit: float,
                     spec: dict | None = None) -> float:
    """The held experts that were hit, read once at their stored width,
    and every pair's token in and its result out."""
    return (hit * expert_params(sz) * sz["param_bytes"]
            + 2.0 * pairs * sz["d"] * ACT_BYTES)


# -- attention -----------------------------------------------------------------


def _mean(values: list) -> float:
    return sum(values) / len(values)


def attn_decode_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer's attention for one micro-step: each live row's 64
    queries against the rows its kind reads, then the weighted values."""
    lens = list(live_lens)
    per_row = 2 * sz["heads"] * (sz["dk"] + sz["dv"])
    return _mean([per_row * sum(_rows(sz, kind, n) for n in lens)
                  for kind in _kinds(sz, spec)])


def attn_decode_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One layer: the K and V rows its kind reads for each live slot,
    once (a KV head's rows serve its whole group of query heads), plus
    the queries in and the outputs out."""
    lens = list(live_lens)
    q_and_o = len(lens) * (sz["qd"] + sz["od"]) * ACT_BYTES
    return _mean([
        sum(_rows(sz, kind, n) for n in lens) * sz["hk"][kind]
        * (sz["dk"] + sz["dv"]) * sz["kv_bytes"] + q_and_o
        for kind in _kinds(sz, spec)])


def attn_prefill_flops(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """One layer's causal attention over a prompt of its TRUE length:
    position ``i`` sees ``min(i + 1, window)`` keys in a window layer."""
    p, w = int(prompt_len), sz["window"]
    seen = {"full": p * (p + 1) // 2,
            "swa": (min(p, w) * (min(p, w) + 1) // 2 + max(p - w, 0) * w)}
    per_pair = 2 * sz["heads"] * (sz["dk"] + sz["dv"])
    return _mean([per_pair * seen[kind] for kind in _kinds(sz, spec)])


def attn_prefill_bytes(sz: dict, prompt_len: int,
                       spec: dict | None = None) -> float:
    """One layer: Q, K and V read and the output written, bfloat16."""
    p = int(prompt_len)
    return _mean([
        p * (sz["qd"] + sz["od"] + sz["hk"][kind] * (sz["dk"] + sz["dv"]))
        * ACT_BYTES for kind in _kinds(sz, spec)])


# -- a whole decode micro-step -------------------------------------------------


def _dense_params(sz: dict) -> int:
    """Matrices every token goes through: attention of every layer, the
    dense FFNs, the routers and the head."""
    d = sz["d"]
    return (sum(attn_params(sz, kind) for kind in sz["kinds"])
            + sum(3 * d * sz["f"] for f in sz["ffns"] if f == "dense")
            + routed_layers(sz) * d * sz["experts"] + d * sz["v"])


def _small_params(sz: dict) -> int:
    """Gains, sinks and selection biases."""
    return (2 * sz["layers"] * sz["d"] + sz["d"]
            + sum(sz["heads"] for k in sz["kinds"] if sz["sink"][k])
            + routed_layers(sz) * sz["experts"])


def decode_step_flops(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One decode micro-step: every live token through the matrices all
    tokens share, its pairs through their experts, and its attention over
    the rows each layer's kind reads."""
    lens = list(live_lens)
    pairs, hit = routing(sz, len(lens), spec)
    return (2.0 * len(lens) * _dense_params(sz)
            + routed_layers(sz) * moe_decode_flops(sz, pairs, hit)
            + sum(attn_decode_flops(sz, lens, {"kind": kind})
                  for kind in sz["kinds"]))


def decode_step_bytes(sz: dict, live_lens: Iterable[int],
                      spec: dict | None = None) -> float:
    """One decode micro-step's least traffic: every shared parameter and
    every expert that was HIT once at its stored width, the live K and V
    rows each kind reads once, and the new rows written."""
    lens = list(live_lens)
    pairs, hit = routing(sz, len(lens), spec)
    params = ((_dense_params(sz) + _small_params(sz)) * sz["param_bytes"]
              + routed_layers(sz) * hit * expert_params(sz)
              * sz["param_bytes"])
    kv = 0.0
    for kind in sz["kinds"]:
        row = sz["hk"][kind] * (sz["dk"] + sz["dv"]) * sz["kv_bytes"]
        kv += (sum(_rows(sz, kind, n) for n in lens) + len(lens)) * row
    return params + kv
