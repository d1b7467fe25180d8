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
from mmlspark_tpu.models.graph import FINAL_NODE, NamedGraph
from mmlspark_tpu.models.registry import register_model
from mmlspark_tpu.ops import kv_cache
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
            # KV-cache decode (models/generate.py): the cache takes this
            # call's K/V at ``pos``; unwritten future positions are
            # invisible either way (causal mask on the dense read,
            # live-length mask in the decode kernels), so one static-shape
            # program serves prefill and decode. What a cache entry is,
            # and how a step writes and reads it: ops/kv_cache.py
            if not self.causal:
                raise ParamError("cache decode requires causal=True")
            if rolled and t != 1:
                raise ParamError(
                    "rolled cache decode is single-token (t=1); "
                    "prefill uses the linear cache path"
                )
            if jnp.ndim(pos) and (rolled or t != 1):
                raise ParamError(
                    "per-row cache positions (the serve engine's fused "
                    "decode step) are single-token and linear-cache only"
                )
            if t == 1 and (decode or rolled):
                # ``attn``: as the trace has always named this decode kernel
                o, new_cache = kv_cache.decode_step(
                    cache, q, k, v, pos, live, window=self.window,
                    name="attn", mesh=self.mesh, rolled=rolled)
            else:
                new_cache = kv_cache.write_rows(cache, k, v, pos)
                if impl == FLASH and isinstance(pos, int) and pos == 0:
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
                    o = dense_attention(q, *new_cache, causal=True,
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
