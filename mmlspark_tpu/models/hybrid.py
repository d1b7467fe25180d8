"""Decoder LM whose layers differ in kind: one block class driven by a
per-layer pattern.

``transformer_lm`` builds every layer alike (LayerNorm, biased GELU MLP,
one fused ``qkv``, one window for all layers). The open models of 2025
mix kinds inside one stack, and this builder takes the mix as data: for
every layer an ATTENTION kind (``full``: causal over the whole context;
``swa``: a causal sliding window with, optionally, a learned per-head
sink in the softmax's denominator) and an FFN kind (``dense``: SwiGLU;
``routed``: sigmoid top-k routing over ``n_experts`` experts of which this
holder has a stated range, :func:`mmlspark_tpu.parallel.expert.
moe_ffn_held`). Around them: RMSNorm, projections without biases, query
and key heads of one width and value heads of another, rotary positions
on the first ``rotary_dim`` dimensions of a head at a base per attention
kind, KV heads per attention kind, an untied head, and parameters stored
in ``param_dtype``.

Every block DECLARES the geometry of its KV cache (:meth:`HybridBlock.
cache_spec`): a full block keeps a row for every position, a window block
a ring of ``window`` rows. The serving pool
(``serve/cache_pool.py``, ``SlotCachePool``) allocates by that declaration,
head-major, which is the layout the decode kernel
(:func:`mmlspark_tpu.ops.flash_attention.flash_decode_grouped`) streams
without a copy.

Products run in bfloat16 with float32 accumulation, norms, the router and
the logits in float32; the residual stream is float32.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import ParamError
from mmlspark_tpu.models.graph import FINAL_NODE, NamedGraph
from mmlspark_tpu.models.registry import register_model
from mmlspark_tpu.models.transformer import (
    ATTN_IMPLS,
    AUTO,
    FLASH,
    resolve_attn_impl,
)
from mmlspark_tpu.ops import kv_cache
from mmlspark_tpu.ops.attention import dense_attention

FULL, SWA = "full", "swa"
DENSE_FFN, ROUTED_FFN = "dense", "routed"


class RMSNorm(nn.Module):
    eps: float = 1e-5
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * scale.astype(jnp.float32)


class TokenEmbed(nn.Module):
    vocab_size: int
    d_model: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids, pos=None):
        # positions are rotary, inside attention; ``pos`` is accepted so
        # the cached forward can hand every block the same arguments
        tok = nn.Embed(self.vocab_size, self.d_model,
                       param_dtype=self.param_dtype, name="token")(ids)
        return tok.astype(jnp.float32)


class HybridAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int        # query and key heads
    v_head_dim: int      # value heads (and the output's, per head)
    window: int | None   # None: full attention
    rope_base: float
    rotary_dim: int
    value_scale: float = 1.0
    sink: bool = False   # a learned logit per query head in the denominator
    attn_impl: str = "dense"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache=None, pos=None, decode=False, live=None):
        from mmlspark_tpu.ops.rope import apply_rope

        b, t, d_model = x.shape
        h, hk, dk, dv = (self.heads, self.kv_heads, self.head_dim,
                         self.v_head_dim)
        x = x.astype(self.dtype)

        def proj(name, width):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        q = proj("q", h * dk)(x).reshape(b, t, h, dk)
        k = proj("k", hk * dk)(x).reshape(b, t, hk, dk)
        v = proj("v", hk * dv)(x).reshape(b, t, hk, dv)
        if self.value_scale != 1.0:
            v = v * jnp.asarray(self.value_scale, v.dtype)
        sink = None
        if self.sink:
            sink = self.param("sink", nn.initializers.zeros, (h,),
                              self.param_dtype).astype(jnp.float32)
        if cache is None:
            positions = None
        elif jnp.ndim(pos):  # the engine's per-row decode step
            positions = jnp.asarray(pos)[:, None] + jnp.arange(t)
        else:
            positions = pos + jnp.arange(t)
        q = apply_rope(q, positions, base=self.rope_base,
                       rotary_dim=self.rotary_dim)
        k = apply_rope(k, positions, base=self.rope_base,
                       rotary_dim=self.rotary_dim)
        kind = FULL if self.window is None else SWA
        new_cache = None
        if cache is None:
            o = self._prompt_attention(q, k, v, sink, kind)
        elif decode and t == 1 and not kv_cache.is_linear(cache):
            # the serving pool's entry, as the pool allocates it by
            # cache_spec: a window block's rows are a ring
            o, new_cache = kv_cache.decode_step(
                cache, q, k, v, pos, live, window=self.window, sink=sink,
                name=f"attn_{kind}_decode")
        else:
            # a linear (B, total, hk, d) cache: prefill, a chunk or a
            # resume against a live prefix, generate()'s decode steps
            new_cache = kv_cache.write_rows(cache, k, v, pos)
            if isinstance(pos, int) and pos == 0:
                # a prefill from position 0 sees this call's own K/V only
                o = self._prompt_attention(q, k, v, sink, kind)
            else:
                o = dense_attention(q, *new_cache, causal=True,
                                    window=self.window, q_offset=pos,
                                    sink=sink)
        out = proj("attn_out", d_model)(o.reshape(b, t, h * dv))
        return out if new_cache is None else (out, new_cache)

    def _prompt_attention(self, q, k, v, sink, kind: str):
        if resolve_attn_impl(self.attn_impl) != FLASH:
            return dense_attention(q, k, v, causal=True, window=self.window,
                                   sink=sink)
        from mmlspark_tpu.ops.flash_attention import flash_attention

        # named apart from ``attn``: the trace tells the kinds apart
        with jax.named_scope(f"attn_{kind}_prefill"):
            return flash_attention(q, k, v, causal=True, window=self.window,
                                   sink=sink)


class _Experts(nn.Module):
    """The held experts' stacked matrices, under a module named
    ``experts`` as EXPERT_RULES' path expects."""

    held: int
    d_model: int
    d_ff: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self):
        init = nn.initializers.normal(0.02)
        up = (self.held, self.d_model, self.d_ff)
        return (self.param("w_gate", init, up, self.param_dtype),
                self.param("w_up", init, up, self.param_dtype),
                self.param("w_down", init,
                           (self.held, self.d_ff, self.d_model),
                           self.param_dtype))


class RoutedFFN(nn.Module):
    n_experts: int          # the router's width
    top_k: int
    d_ff: int
    first: int              # the held experts: first .. first + held - 1
    held: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, valid=None):
        from mmlspark_tpu.parallel.expert import moe_ffn_held

        d = x.shape[-1]
        router = self.param("router", nn.initializers.normal(0.02),
                            (d, self.n_experts), self.param_dtype)
        bias = self.param("select_bias", nn.initializers.zeros,
                          (self.n_experts,), self.param_dtype)
        w_gate, w_up, w_down = _Experts(self.held, d, self.d_ff,
                                        self.param_dtype, name="experts")()
        # the router reads the normed stream in float32, the experts in
        # the compute dtype
        return moe_ffn_held(
            x, router, bias, w_gate.astype(self.dtype),
            w_up.astype(self.dtype), w_down.astype(self.dtype),
            top_k=self.top_k, first=self.first, valid=valid,
        )


class HybridBlock(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    v_head_dim: int
    window: int | None
    rope_base: float
    rotary_dim: int
    value_scale: float
    sink: bool
    ffn: str                 # DENSE_FFN | ROUTED_FFN
    d_ff: int
    n_experts: int = 0
    top_k: int = 0
    held: tuple = (0, 0)     # (first, count) of the experts held here
    eps: float = 1e-5
    attn_impl: str = "dense"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def cache_spec(self) -> tuple:
        """``(kind, rows, kv_heads, key width, value width)``: what the
        pool holds for this block. A full block keeps every position
        (``rows`` None: the pool's ``cache_len``), a window block a ring
        of ``window`` rows."""
        kind, rows = ((kv_cache.FULL_ROWS, None) if self.window is None
                      else (kv_cache.RING_ROWS, int(self.window)))
        return (kind, rows, self.kv_heads, self.head_dim, self.v_head_dim)

    @property
    def routed(self) -> bool:
        return self.ffn == ROUTED_FFN

    @nn.compact
    def __call__(self, x, cache=None, pos=None, rolled=False,
                 decode=False, live=None, valid=None):
        if rolled:
            raise ParamError(
                "a hybrid block rolls no cache of its own: generate() "
                "keeps it linear, the serving pool keeps the ring"
            )
        y = RMSNorm(self.eps, self.param_dtype, name="ln1")(x)
        attn = HybridAttention(
            self.heads, self.kv_heads, self.head_dim, self.v_head_dim,
            self.window, self.rope_base, self.rotary_dim, self.value_scale,
            self.sink, self.attn_impl, self.dtype, self.param_dtype,
            name="attn",
        )(y, cache=cache, pos=pos, decode=decode, live=live)
        new_cache = None
        if cache is not None:
            attn, new_cache = attn
        x = x + attn
        y = RMSNorm(self.eps, self.param_dtype, name="ln2")(x)
        counters = None
        if self.routed:
            y, counters = RoutedFFN(
                self.n_experts, self.top_k, self.d_ff, self.held[0],
                self.held[1], self.dtype, self.param_dtype, name="moe",
            )(y, valid)
        else:
            def dense(name, width):
                return nn.Dense(width, use_bias=False, dtype=self.dtype,
                                param_dtype=self.param_dtype, name=name)

            y = y.astype(self.dtype)
            y = dense("mlp_out", x.shape[-1])(
                nn.silu(dense("mlp_gate", self.d_ff)(y))
                * dense("mlp_up", self.d_ff)(y)
            )
        out = x + y
        if new_cache is None:
            return out
        if counters is None:
            return out, new_cache
        return out, new_cache, counters


class HybridHead(nn.Module):
    vocab_size: int
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = RMSNorm(self.eps, self.param_dtype, name="ln_f")(x)
        x = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="head")(
            x.astype(self.dtype))
        return x.astype(jnp.float32)


def _dtype(name):
    if isinstance(name, str):
        try:
            return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]
        except KeyError:
            raise ParamError(
                f"param_dtype must be 'float32' or 'bfloat16', got {name!r}"
            ) from None
    return name


@register_model("hybrid_lm")
def hybrid_lm(
    vocab_size: int = 1024,
    d_model: int = 128,
    heads: int = 4,
    head_dim: int = 32,
    v_head_dim: int | None = None,
    attention: tuple = (FULL, SWA),
    ffn: tuple = (DENSE_FFN, ROUTED_FFN),
    kv_heads: int = 1,
    swa_kv_heads: int | None = None,
    window: int = 128,
    rope_base: float = 10000.0,
    swa_rope_base: float | None = None,
    rotary_dim: int | None = None,
    value_scale: float = 1.0,
    swa_sink: bool = False,
    full_sink: bool = False,
    d_ff: int = 0,
    n_experts: int = 8,
    top_k: int = 2,
    expert_d_ff: int = 0,
    held_experts: tuple | None = None,
    norm_eps: float = 1e-5,
    max_len: int = 512,
    attn_impl: str = AUTO,
    param_dtype: Any = "float32",
) -> NamedGraph:
    """Causal decoder LM with a per-layer pattern: ``attention[i]`` in
    (``"full"``, ``"swa"``) and ``ffn[i]`` in (``"dense"``, ``"routed"``)
    give layer ``i`` its kinds. ``kv_heads``, ``rope_base`` and
    ``full_sink`` are the full layers', ``swa_kv_heads``,
    ``swa_rope_base``, ``swa_sink`` and ``window`` the window layers'.
    ``held_experts = (first, count)`` says which of the router's
    ``n_experts`` experts this holder has (default: all)."""
    attention, ffn = tuple(attention), tuple(ffn)
    if not attention or len(attention) != len(ffn):
        raise ParamError(
            f"attention ({len(attention)} layers) and ffn ({len(ffn)}) "
            "give every layer its kinds: same length, at least one"
        )
    for kind in attention:
        if kind not in (FULL, SWA):
            raise ParamError(
                f"attention kinds are '{FULL}' and '{SWA}', got {kind!r}")
    for kind in ffn:
        if kind not in (DENSE_FFN, ROUTED_FFN):
            raise ParamError(
                f"ffn kinds are '{DENSE_FFN}' and '{ROUTED_FFN}', got "
                f"{kind!r}")
    v_head_dim = v_head_dim or head_dim
    swa_kv_heads = swa_kv_heads or kv_heads
    for name, hk in (("kv_heads", kv_heads), ("swa_kv_heads", swa_kv_heads)):
        if hk < 1 or heads % hk:
            raise ParamError(
                f"{name} ({hk}) must be >= 1 and divide heads ({heads})")
    rotary_dim = head_dim if rotary_dim is None else int(rotary_dim)
    if rotary_dim % 2 or not 0 < rotary_dim <= head_dim:
        raise ParamError(
            f"rotary_dim must be even and in (0, head_dim={head_dim}], "
            f"got {rotary_dim}")
    if int(window) < 1:
        raise ParamError(f"window must be >= 1, got {window}")
    if attn_impl not in ATTN_IMPLS:
        raise ParamError(
            f"unknown attn_impl '{attn_impl}'; one of {ATTN_IMPLS}")
    attn_impl = resolve_attn_impl(attn_impl)
    first, count = held_experts or (0, n_experts)
    if ROUTED_FFN in ffn and not (
        1 <= top_k <= n_experts and count >= 1
        and 0 <= first and first + count <= n_experts
    ):
        raise ParamError(
            f"routing needs 1 <= top_k ({top_k}) <= n_experts "
            f"({n_experts}) and held experts [{first}, {first + count}) "
            "inside them")
    d_ff = d_ff or 4 * d_model
    expert_d_ff = expert_d_ff or d_ff
    dtype = _dtype(param_dtype)
    blocks: list[tuple[str, Any]] = [
        ("embed", TokenEmbed(vocab_size, d_model, dtype))
    ]
    for i, (a_kind, f_kind) in enumerate(zip(attention, ffn)):
        swa = a_kind == SWA
        routed = f_kind == ROUTED_FFN
        blocks.append((f"block{i}", HybridBlock(
            heads=heads, kv_heads=swa_kv_heads if swa else kv_heads,
            head_dim=head_dim, v_head_dim=v_head_dim,
            window=int(window) if swa else None,
            rope_base=float(
                (swa_rope_base or rope_base) if swa else rope_base),
            rotary_dim=rotary_dim, value_scale=float(value_scale),
            sink=bool(swa_sink if swa else full_sink), ffn=f_kind,
            d_ff=expert_d_ff if routed else d_ff,
            n_experts=n_experts if routed else 0,
            top_k=top_k if routed else 0,
            held=(first, count) if routed else (0, 0),
            eps=norm_eps, attn_impl=attn_impl, param_dtype=dtype,
        )))
    blocks.append((FINAL_NODE, HybridHead(vocab_size, norm_eps,
                                          param_dtype=dtype)))
    return NamedGraph(
        name="hybrid_lm",
        blocks=blocks,
        input_shape=(max_len,),
        extra={
            "vocab_size": vocab_size,
            "attn_impl": attn_impl,
            "causal": True,
            "heads": heads,
            # no one window for all layers: each block declares its own
            "window": None,
            "pos_embedding": "rope",
            "attention": attention,
            "ffn": ffn,
            "n_experts": n_experts if ROUTED_FFN in ffn else 0,
            # per-token dropless routing is causal: a pad routes nowhere
            # and takes nothing from a real token
            "routing_drops": False,
        },
    )
