"""Transformer LM / encoder family with pluggable parallel attention.

Capability upgrade beyond the reference (which has no attention anywhere —
SURVEY.md §5): the long-context and multi-chip design the task requires.
One model family covers:

- single-chip dense attention (XLA-fused),
- ring attention (context parallelism over the ``seq`` mesh axis),
- Ulysses all-to-all sequence parallelism,

selected by ``attn_impl`` — the module code is identical; only the
attention call changes. Tensor parallelism comes from sharding rules
(:data:`mmlspark_tpu.parallel.sharding.TRANSFORMER_TP_RULES`): layer names
``qkv`` / ``attn_out`` / ``mlp_in`` / ``mlp_out`` are the contract those
regexes match.

Compute is bfloat16 (MXU-native), params float32, logits float32.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import ParamError
from mmlspark_tpu.models.generate import HeadMajorKV
from mmlspark_tpu.models.graph import FINAL_NODE, NamedGraph
from mmlspark_tpu.models.registry import register_model
from mmlspark_tpu.ops.attention import dense_attention

DENSE = "dense"
RING = "ring"
ULYSSES = "ulysses"
FLASH = "flash"
AUTO = "auto"
ATTN_IMPLS = (DENSE, RING, ULYSSES, FLASH, AUTO)


def resolve_attn_impl(attn_impl: str) -> str:
    """``auto`` -> the Pallas flash kernel on TPU (O(S·d) memory both
    directions, ops/flash_attention.py), XLA dense elsewhere (the
    interpreter-mode kernel would crawl on CPU test meshes)."""
    if attn_impl != AUTO:
        return attn_impl
    from mmlspark_tpu.core.env import is_tpu

    return FLASH if is_tpu() else DENSE


class TokenPosEmbed(nn.Module):
    vocab_size: int
    d_model: int
    max_len: int
    learned_pos: bool = True  # False: tokens only (RoPE in attention)

    @nn.compact
    def __call__(self, ids, pos=None):
        # ids: (B, T) int; ``pos`` (traced scalar, or a (B,) vector of
        # PER-ROW offsets for the serving engine's multi-tenant decode)
        # offsets the position table for cached decode, where T is the
        # step width not the absolute position
        tok = nn.Embed(self.vocab_size, self.d_model,
                       param_dtype=jnp.float32, name="token")(ids)
        if not self.learned_pos:
            return tok
        table = self.param(
            "pos", nn.initializers.normal(0.02),
            (self.max_len, self.d_model), jnp.float32,
        )
        if pos is None:
            return tok + table[None, : ids.shape[1]]
        if jnp.ndim(pos):  # per-row offsets: gather (B, T) table rows
            positions = jnp.asarray(pos)[:, None] + jnp.arange(ids.shape[1])
            return tok + jnp.take(table, positions, axis=0)
        rows = jax.lax.dynamic_slice(
            table, (pos, 0), (ids.shape[1], self.d_model)
        )
        return tok + rows[None]


class SelfAttention(nn.Module):
    heads: int
    head_dim: int
    causal: bool
    attn_impl: str = DENSE
    window: int | None = None  # causal sliding window (all impls)
    kv_heads: int | None = None  # grouped-query attention (None = MHA)
    rope: bool = False  # rotary position embeddings on q/k
    mesh: Any = None  # jax.sharding.Mesh (hashable -> valid static attr)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, cache=None, pos=None, rolled=False,
                 decode=False, live=None):
        b, t, _ = x.shape
        h, d = self.heads, self.head_dim
        hk = self.kv_heads or h
        x = x.astype(self.dtype)
        # one fused projection; under GQA the K/V slices are narrower
        # (hk heads), shrinking both the projection and the KV tensors
        qkv = nn.Dense((h + 2 * hk) * d, dtype=self.dtype,
                       param_dtype=jnp.float32, name="qkv")(x)
        qkv = qkv.reshape(b, t, h + 2 * hk, d)
        q = qkv[:, :, :h]
        k = qkv[:, :, h:h + hk]
        v = qkv[:, :, h + hk:]
        if self.rope:
            from mmlspark_tpu.ops.rope import apply_rope

            if cache is None:
                positions = None
            elif jnp.ndim(pos):  # per-row serve decode: (B, T) positions
                positions = jnp.asarray(pos)[:, None] + jnp.arange(t)
            else:
                positions = pos + jnp.arange(t)
            q = apply_rope(q, positions)
            k = apply_rope(k, positions)
        if self.attn_impl not in ATTN_IMPLS:
            raise ParamError(
                f"unknown attn_impl '{self.attn_impl}'; one of {ATTN_IMPLS}"
            )
        impl = resolve_attn_impl(self.attn_impl)
        new_cache = None
        if cache is not None:
            # KV-cache decode (models/generate.py): the preallocated
            # (B, total, hk, d) buffers take this step's K/V at ``pos``;
            # unwritten future positions are invisible either way —
            # causal mask (q_offset=pos) on the dense read, live-length
            # mask in the decode kernel — so one static-shape program
            # serves both prefill (t = prompt len, pos = 0) and decode
            # (t = 1). The impl dispatch above is a *training/scoring*
            # choice; decode reads are bandwidth-bound, which is exactly
            # why single-token steps route to the length-aware split-KV
            # kernel below: it skips the HBM traffic for dead cache
            # blocks instead of reorganizing compute.
            if not self.causal:
                raise ParamError("cache decode requires causal=True")
            if rolled and t != 1:
                raise ParamError(
                    "rolled cache decode is single-token (t=1); "
                    "prefill uses the linear cache path"
                )
            per_row = bool(jnp.ndim(pos))
            if per_row and (rolled or t != 1):
                raise ParamError(
                    "per-row cache positions (the serve engine's fused "
                    "decode step) are single-token and linear-cache only"
                )
            if len(cache) in (3, 5):
                # PAGED slot cache (mmlspark_tpu/serve/paging.py): K/V
                # are physical page stores (num_pages, hk, page_size, d)
                # shared by all rows, plus a (B, max_pages) page table
                # mapping each row's logical positions through its pages.
                # The 5-tuple is the int8 page store: two extra
                # (num_pages, hk) f32 per-page scale leaves. This is
                # strictly the serve engine's fused decode-block
                # format — prefill runs on a linear batch-1 cache and
                # the pool scatters it into pages host-side.
                if not (per_row and decode and t == 1):
                    raise ParamError(
                        "paged caches serve per-row single-token decode "
                        "only (the serve engine's fused decode step); "
                        "prefill uses the linear cache path"
                    )
                ck, cv, ptab, *cscales = cache
                ps = ck.shape[2]
                virt = ptab.shape[1] * ps
                if self.window is not None and self.window < virt:
                    raise ParamError(
                        f"paged decode has no windowed read: window "
                        f"({self.window}) must cover the virtual cache "
                        f"({virt})"
                    )
                # scatter this step's K/V through the table: row b's
                # position pos[b] lands in physical page
                # ptab[b, pos // ps] at offset pos % ps. Dead rows hold
                # a frozen pos whose page the pool keeps pointed at a
                # trash page, so their writes never touch live data.
                rows = jnp.arange(b)
                pages = ptab[rows, pos // ps]
                offs = pos % ps
                hidx = jnp.arange(ck.shape[1])
                if cscales:
                    # int8 page store: a page's scale is FIXED at its
                    # first write — offs == 0 means this token opens a
                    # fresh page (ensure_decode_pages pre-mapped it),
                    # so its amax (+ headroom) becomes the page's
                    # scale; later tokens into the page quantize
                    # against it and saturate into the error budget.
                    # Dead rows re-stamp their trash page's scale,
                    # which nothing ever reads (live length 0).
                    from mmlspark_tpu.serve.cache_pool import (
                        kv_head_scales, quantize_kv,
                    )

                    ks, vs = cscales
                    tk = k[:, 0].astype(jnp.float32)
                    tv = v[:, 0].astype(jnp.float32)
                    first = (offs == 0)[:, None]
                    row_ks = jnp.where(
                        first, kv_head_scales(tk, axes=(2,)), ks[pages]
                    )
                    row_vs = jnp.where(
                        first, kv_head_scales(tv, axes=(2,)), vs[pages]
                    )
                    ks = ks.at[pages].set(row_ks)
                    vs = vs.at[pages].set(row_vs)
                    cscales = [ks, vs]
                    wk = quantize_kv(tk, row_ks)
                    wv = quantize_kv(tv, row_vs)
                else:
                    wk = k[:, 0].astype(ck.dtype)
                    wv = v[:, 0].astype(cv.dtype)
                ck = ck.at[pages[:, None], hidx[None, :], offs[:, None]
                           ].set(wk)
                cv = cv.at[pages[:, None], hidx[None, :], offs[:, None]
                           ].set(wv)
                new_cache = (ck, cv, ptab, *cscales)
                from mmlspark_tpu.ops.attention import decode_live_lengths
                from mmlspark_tpu.ops.flash_attention import (
                    paged_flash_decode,
                )

                o = paged_flash_decode(
                    q, ck, cv, decode_live_lengths(pos, b, live=live),
                    ptab,
                    k_scale=cscales[0] if cscales else None,
                    v_scale=cscales[1] if cscales else None,
                    mesh=self.mesh,
                )
            elif isinstance(cache, HeadMajorKV):
                # the one-device bf16 slot pool (serve/cache_pool.py):
                # (S, hk, cache_len, d) rows, which the pool says with
                # the entry's type. The step's row is written in place
                # and the kernel streams (rows, d) tiles of each KV
                # head: no relayout of the pool on either side
                if not (per_row and decode and t == 1) or (
                        self.window is not None
                        and self.window < cache.k.shape[2]):
                    raise ParamError(
                        "head-major caches serve per-row single-token "
                        "full-window decode only (the serve engine's "
                        "fused decode step); prefill uses the linear "
                        "cache path"
                    )
                from mmlspark_tpu.ops.attention import decode_live_lengths
                from mmlspark_tpu.ops.flash_attention import (
                    cache_row_write,
                    flash_decode_grouped,
                )

                # (b, hk, d) -> the entry's own heads and width: packed
                # rows hold adjacent heads side by side (lane_pack)
                packed = (b, cache.k.shape[1], -1)
                new_cache = HeadMajorKV(*cache_row_write(
                    *cache, k[:, 0].reshape(packed), v[:, 0].reshape(packed),
                    pos))
                # named as the trace has always shown this module's
                # decode kernel (``attn.N``), where the decode metrics
                # look: the kernel is jitted where it stands, so the
                # module's scope no longer names it
                o = flash_decode_grouped(
                    q, *new_cache, decode_live_lengths(pos, b, live=live),
                    name="attn")
            else:
                ck, cv, *cscales = cache
                if cscales and not (
                    per_row and decode and t == 1
                    and (self.window is None
                         or self.window >= ck.shape[1])
                ):
                    # the 4-tuple is the slot pool's int8 mode; only
                    # the flash-decode read below can dequantize it
                    raise ParamError(
                        "int8 dense caches serve the engine's per-row "
                        "single-token full-window decode only; prefill "
                        "and single-request generate use bf16 linear "
                        "caches"
                    )
                if per_row:
                    # multi-tenant decode (mmlspark_tpu.serve): every
                    # batch row is a different request writing its own
                    # absolute position in its own slot buffer
                    rows = jnp.arange(b)
                    if cscales:
                        # quantize the step's K/V against the slots'
                        # prefill-fixed scales (out-of-range values
                        # saturate — priced into the parity budget)
                        from mmlspark_tpu.serve.cache_pool import (
                            quantize_kv,
                        )

                        wk = quantize_kv(k[:, 0], cscales[0])
                        wv = quantize_kv(v[:, 0], cscales[1])
                    else:
                        wk = k[:, 0].astype(ck.dtype)
                        wv = v[:, 0].astype(cv.dtype)
                    ck = ck.at[rows, pos].set(wk)
                    cv = cv.at[rows, pos].set(wv)
                else:
                    # rolled (O(window) circular, sliding-window models
                    # on long generations): this step's K/V land at slot
                    # pos % W — every written slot is inside the window
                    # by construction (ops/attention.py
                    # rolled_window_attention). Linear: the write index
                    # IS the absolute position.
                    idx = pos % ck.shape[1] if rolled else pos
                    ck = jax.lax.dynamic_update_slice(
                        ck, k.astype(ck.dtype), (0, idx, 0, 0)
                    )
                    cv = jax.lax.dynamic_update_slice(
                        cv, v.astype(cv.dtype), (0, idx, 0, 0)
                    )
                new_cache = (ck, cv, *cscales)
                if rolled:
                    from mmlspark_tpu.ops.attention import (
                        rolled_window_attention,
                    )

                    o = rolled_window_attention(q, ck, cv, pos)
                elif decode and t == 1 and (
                    self.window is None or self.window >= ck.shape[1]
                ):
                    # single-token DECODE step over a linear cache: the
                    # length-aware split-KV kernel reads only each row's
                    # LIVE positions [0, pos+1) — per-row work O(pos),
                    # not O(cache_len) — instead of a dense read of the
                    # whole buffer. Window models reach here only when
                    # the window covers the buffer (masking would be a
                    # no-op); a tighter window uses the rolled path or
                    # dense fallback.
                    from mmlspark_tpu.ops.attention import (
                        decode_live_lengths,
                    )
                    from mmlspark_tpu.ops.flash_attention import (
                        flash_decode,
                    )

                    # ``live`` (the serve engine's fused decode-block
                    # carry) zeroes dead rows' lengths, so the kernel's
                    # early-out skips their cache traffic mid-block
                    o = flash_decode(
                        q, ck, cv,
                        decode_live_lengths(pos, b, live=live),
                        k_scale=cscales[0] if cscales else None,
                        v_scale=cscales[1] if cscales else None,
                        mesh=self.mesh,
                    )
                elif impl == FLASH and isinstance(pos, int) and pos == 0:
                    # a PREFILL from position 0 (static, so this is
                    # decided at trace time) sees exactly this call's
                    # own K/V — the cache beyond t is unwritten and
                    # causally invisible — so it runs the same flash
                    # kernel as scoring instead of materializing the
                    # (t, cache) score matrix. Resume/chunk prefills
                    # (traced pos, a live prefix in the cache) keep the
                    # dense read: the kernel has no query offset.
                    from mmlspark_tpu.ops.flash_attention import (
                        flash_attention,
                    )

                    o = flash_attention(q, k, v, causal=True,
                                        window=self.window, mesh=self.mesh)
                else:
                    o = dense_attention(q, ck, cv, causal=True,
                                        window=self.window, q_offset=pos)
        elif impl == FLASH:
            from mmlspark_tpu.ops.flash_attention import flash_attention

            o = flash_attention(q, k, v, causal=self.causal,
                                window=self.window, mesh=self.mesh)
        elif impl == DENSE or self.mesh is None:
            # ring/ulysses degrade to dense when no mesh is provided
            o = dense_attention(q, k, v, causal=self.causal,
                                window=self.window)
        elif impl == RING:
            from mmlspark_tpu.parallel.context_parallel import ring_attention

            o = ring_attention(q, k, v, self.mesh, causal=self.causal,
                               window=self.window)
        elif impl == ULYSSES:
            from mmlspark_tpu.parallel.context_parallel import (
                ulysses_attention,
            )

            o = ulysses_attention(q, k, v, self.mesh, causal=self.causal,
                                  window=self.window)
        else:  # unreachable: impl validated + resolved above
            raise ParamError(f"unhandled attn_impl '{impl}'")
        out = nn.Dense(x.shape[-1], dtype=self.dtype,
                       param_dtype=jnp.float32, name="attn_out")(
            o.reshape(b, t, h * d)
        )
        return out if new_cache is None else (out, new_cache)


class Block(nn.Module):
    heads: int
    head_dim: int
    d_ff: int
    causal: bool
    attn_impl: str
    mesh: Any
    dtype: Any = jnp.bfloat16
    window: int | None = None
    kv_heads: int | None = None
    rope: bool = False

    @nn.compact
    def __call__(self, x, cache=None, pos=None, rolled=False,
                 decode=False, live=None):
        y = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
        attn = SelfAttention(
            self.heads, self.head_dim, self.causal, self.attn_impl,
            window=self.window, kv_heads=self.kv_heads, rope=self.rope,
            mesh=self.mesh, dtype=self.dtype, name="attn",
        )(y, cache=cache, pos=pos, rolled=rolled, decode=decode, live=live)
        new_cache = None
        if cache is not None:
            attn, new_cache = attn
        x = x + attn
        y = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        y = nn.Dense(self.d_ff, dtype=self.dtype, param_dtype=jnp.float32,
                     name="mlp_in")(y.astype(self.dtype))
        y = nn.gelu(y)
        y = nn.Dense(x.shape[-1], dtype=self.dtype, param_dtype=jnp.float32,
                     name="mlp_out")(y)
        out = x + y
        return out if new_cache is None else (out, new_cache)


class LMHead(nn.Module):
    vocab_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        x = nn.Dense(self.vocab_size, dtype=self.dtype,
                     param_dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


def validate_attention_features(*, heads: int, head_dim: int,
                                causal: bool, window: int | None,
                                kv_heads: int | None,
                                pos_embedding: str) -> bool:
    """Shared build-time validation for the attention feature set
    (transformer_lm AND transformer_lm_moe use the same rules); returns
    whether RoPE is enabled."""
    if window is not None:
        if not causal:
            raise ParamError(
                "window (causal sliding-window attention) requires "
                "causal=True"
            )
        if int(window) < 1:
            raise ParamError(f"window must be >= 1, got {window}")
    if kv_heads is not None and (kv_heads < 1 or heads % kv_heads):
        raise ParamError(
            f"kv_heads ({kv_heads}) must be >= 1 and divide heads "
            f"({heads})"
        )
    if pos_embedding not in ("learned", "rope"):
        raise ParamError(
            f"pos_embedding must be 'learned' or 'rope', got "
            f"'{pos_embedding}'"
        )
    if pos_embedding == "rope" and head_dim % 2:
        raise ParamError(
            f"RoPE needs an even head_dim, got {head_dim}"
        )
    return pos_embedding == "rope"


@register_model("transformer_lm")
def transformer_lm(
    vocab_size: int = 1024,
    d_model: int = 128,
    heads: int = 4,
    depth: int = 2,
    d_ff: int = 0,
    max_len: int = 512,
    causal: bool = True,
    attn_impl: str = AUTO,
    window: int | None = None,
    kv_heads: int | None = None,
    pos_embedding: str = "learned",
    mesh: Any = None,
) -> NamedGraph:
    """Decoder-only LM (or bidirectional encoder with ``causal=False``);
    per-token logits, so it also serves as the long-context sequence
    tagger (the BiLSTM capability, scaled). ``window=W`` enables the
    flash kernel's causal sliding window (O(S·W) attention work)."""
    if d_model % heads:
        raise ParamError(f"d_model {d_model} not divisible by heads {heads}")
    rope = validate_attention_features(
        heads=heads, head_dim=d_model // heads, causal=causal,
        window=window, kv_heads=kv_heads, pos_embedding=pos_embedding,
    )
    if attn_impl not in ATTN_IMPLS:
        raise ParamError(
            f"unknown attn_impl '{attn_impl}'; one of {ATTN_IMPLS}"
        )
    attn_impl = resolve_attn_impl(attn_impl)
    d_ff = d_ff or 4 * d_model
    blocks: list[tuple[str, Any]] = [
        ("embed", TokenPosEmbed(vocab_size, d_model, max_len,
                                learned_pos=not rope))
    ]
    for i in range(depth):
        blocks.append(
            (
                f"block{i}",
                Block(heads, d_model // heads, d_ff, causal, attn_impl,
                      mesh, window=window, kv_heads=kv_heads, rope=rope),
            )
        )
    blocks.append((FINAL_NODE, LMHead(vocab_size)))
    return NamedGraph(
        name="transformer_lm",
        blocks=blocks,
        input_shape=(max_len,),
        extra={
            "vocab_size": vocab_size,
            "attn_impl": attn_impl,
            "causal": causal,
            "heads": heads,
            "window": window,
            "kv_heads": kv_heads,
            "pos_embedding": pos_embedding,
        },
    )
