"""Decoder LM whose layers differ in kind: one block class driven by a
per-layer pattern.

``transformer_lm`` builds every layer alike (LayerNorm, biased GELU MLP,
one fused ``qkv``, one window for all layers). The open models of 2025
mix kinds inside one stack, and this builder takes the mix as data: for
every layer an OPERATOR kind (``full``: attention, causal over the whole
context; ``swa``: a causal sliding window with, optionally, a learned
per-head sink in the softmax's denominator; ``mla``: multi-head latent
attention, causal over the whole context, whose keys and values are
up-projections of ONE compressed row a position,
:class:`LatentAttention`; ``conv``: no attention at all, a gated short
convolution over the last ``conv_kernel`` positions,
:class:`ShortConv`) and an FFN kind (``dense``: SwiGLU; ``routed``:
top-k routing, sigmoid with a selection bias or softmax over all
(``router``), over ``n_experts`` experts of which this holder has a
stated range,
:func:`mmlspark_tpu.parallel.expert.moe_ffn_held`, beside an always-on
shared SwiGLU where ``shared_d_ff`` gives one). Around them: RMSNorm,
projections without biases, query and key heads of one width and value
heads of another, rotary positions on the first ``rotary_dim`` dimensions
of a head (a latent layer's: on its rotary part alone) at a base per
attention kind, KV heads per attention kind, optionally an RMSNorm over
every query and key head before the rotation (``qk_norm``), an untied
head, and parameters stored in ``param_dtype``.

Every block DECLARES the geometry of its KV cache (:meth:`HybridBlock.
cache_spec`): a full block keeps a row for every position, a window block
a ring of ``window`` rows, a latent block ONE row ``[c ; k_rope]`` for
every position and all heads, a convolution block a STATE of
``conv_kernel - 1`` rows a slot whatever its length. The serving pool
(``serve/cache_pool.py``, ``SlotCachePool``) allocates by that declaration,
head-major, which is the layout the decode kernel
(:func:`mmlspark_tpu.ops.flash_attention.flash_decode_grouped`) streams
without a copy.

A stack whose ``block`` is L > 0 GENERATES BY DIFFUSION OVER BLOCKS of L
positions on a grid from position 0 (the SDAR line): its attention is
BLOCK-causal, position ``i`` seeing ``j`` iff ``j // L <= i // L``, in a
prompt's forward and in a denoising step alike, and a step runs a block's
L rows at once against the slot's clean prefix and the block itself, or
2L rows, a block's clean close beside the next block's first step
(:func:`mmlspark_tpu.models.generate.make_denoise_block`; ``mask_id`` is
what a position not yet committed reads).

Products run in bfloat16 with float32 accumulation, norms, the router and
the logits in float32; the residual stream is float32.
"""

from __future__ import annotations

from functools import partial
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

FULL, SWA, MLA, CONV = "full", "swa", "mla", "conv"
#: the kernels' name for a block-causal full layer (``attn_block_prefill``,
#: ``attn_block_decode``)
BLOCK = "block"
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
    rope_interleave: bool = False
    # an RMSNorm over each query and each key head's ``head_dim`` numbers
    # (one gain for all heads of a kind), before the rotation
    qk_norm: bool = False
    eps: float = 1e-5
    # > 0: block-causal over blocks of this many positions (a model that
    # generates by diffusion over blocks); full attention only
    block: int = 0

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
        if self.qk_norm:
            q = RMSNorm(self.eps, self.param_dtype, name="q_norm")(
                q).astype(self.dtype)
            k = RMSNorm(self.eps, self.param_dtype, name="k_norm")(
                k).astype(self.dtype)
        sink = None
        if self.sink:
            sink = self.param("sink", nn.initializers.zeros, (h,),
                              self.param_dtype).astype(jnp.float32)
        positions = _positions(cache, pos, t)
        q = apply_rope(q, positions, base=self.rope_base,
                       rotary_dim=self.rotary_dim,
                       interleave=self.rope_interleave)
        k = apply_rope(k, positions, base=self.rope_base,
                       rotary_dim=self.rotary_dim,
                       interleave=self.rope_interleave)
        kind = BLOCK if self.block else FULL if self.window is None else SWA
        new_cache = None
        if cache is None:
            o = self._prompt_attention(q, k, v, sink, kind)
        elif self.block and kv_cache.is_linear(cache) and not (
                isinstance(pos, int) and pos == 0):
            raise ParamError(
                "a block-causal layer's cached forward is a prompt's from "
                "position 0 or a denoising step over the serving pool")
        elif decode and (t == 1 or self.block) and not kv_cache.is_linear(
                cache):
            # the serving pool's entry, as the pool allocates it by
            # cache_spec: a window block's rows are a ring. A block-causal
            # step of two blocks' rows is a block's close beside the next
            # block's step: the first block's rows do not see the second's
            if self.block and t not in (self.block, 2 * self.block):
                raise ParamError(
                    f"a denoising step runs one block of {self.block} rows "
                    f"a slot or two, got {t}")
            o, new_cache = kv_cache.decode_step(
                cache, q, k, v, pos, live, window=self.window, sink=sink,
                name=f"attn_{kind}_decode",
                lead=t - self.block if self.block else 0)
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
        return _prompt_attention(self.attn_impl, q, k, v, kind,
                                 window=self.window, sink=sink,
                                 causal_block=self.block or 1)


def _positions(cache, pos, t: int):
    """The positions of a call's ``t`` tokens: 0.. without a cache, from
    ``pos`` with one ((B,) per-row: the engine's fused decode step)."""
    if cache is None:
        return None
    if jnp.ndim(pos):
        return jnp.asarray(pos)[:, None] + jnp.arange(t)
    return pos + jnp.arange(t)


def _prompt_attention(attn_impl: str, q, k, v, kind: str, *, window=None,
                      sink=None, causal_block: int = 1):
    """A prompt's causal attention over its own K/V, by the flash forward
    kernel where the block runs it; block-causal over blocks of
    ``causal_block`` positions where that is > 1."""
    if resolve_attn_impl(attn_impl) != FLASH:
        return dense_attention(q, k, v, causal=True, window=window,
                               sink=sink, causal_block=causal_block)
    from mmlspark_tpu.ops.flash_attention import flash_attention

    # named apart from ``attn``: the trace tells the kinds apart
    with jax.named_scope(f"attn_{kind}_prefill"):
        return flash_attention(q, k, v, causal=True, window=window,
                               sink=sink, causal_block=causal_block)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3's MLA, without the
    query's low rank). ``q = x W_q`` as ``heads`` of ``[nope ; rope]``;
    ``[c_raw ; k_rope] = x W_kva``, ``c = RMSNorm(c_raw)``, the one
    ``k_rope`` shared by all heads; ``[k_nope_h ; v_h] = c W_kvb`` a
    head. Rotary positions on ``q_rope`` and ``k_rope`` alone.

    What is CACHED is the latent row ``[c ; k_rope]`` (after the norm and
    the rotation): ``kv_lora_rank + rope_dim`` numbers a position for all
    heads (:class:`mmlspark_tpu.ops.kv_cache.LatentRows`). A PREFILL
    from position 0 EXPANDS: per-head keys ``[k_nope_h ; k_rope]`` and
    values through the flash forward kernel, and hands the latent rows
    to the cache. Every other call ABSORBS: ``W_kvb``'s key half is
    folded into the query (``q'_h = [q_nope_h W_UK_h^T ; q_rope_h]``),
    the scores and the weighted sum run over the cached rows themselves
    (values = the rows' first ``kv_lora_rank`` columns), and the value
    half is applied after the sum (``o_h = o_lat_h W_UV_h``): the same
    attention, and no per-head key or value is ever made for a cached
    position."""

    heads: int
    nope_dim: int
    rope_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_base: float
    rope_interleave: bool = False
    eps: float = 1e-5
    attn_impl: str = "dense"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache=None, pos=None, decode=False, live=None):
        from mmlspark_tpu.ops.rope import apply_rope

        b, t, d_model = x.shape
        h, dn, dr, dv, rank = (self.heads, self.nope_dim, self.rope_dim,
                               self.v_head_dim, self.kv_lora_rank)
        x = x.astype(self.dtype)

        def proj(name, width):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        def product(spec, a, w):
            return jnp.einsum(spec, a, w,
                              preferred_element_type=jnp.float32
                              ).astype(self.dtype)

        q = proj("q", h * (dn + dr))(x).reshape(b, t, h, dn + dr)
        kv_a = proj("kv_a", rank + dr)(x)
        c = RMSNorm(self.eps, self.param_dtype, name="kv_norm")(
            kv_a[..., :rank]).astype(self.dtype)
        w_kvb = self.param(
            "kv_b", nn.initializers.normal(0.02), (rank, h * (dn + dv)),
            self.param_dtype).astype(self.dtype).reshape(rank, h, dn + dv)
        positions = _positions(cache, pos, t)
        rotate = partial(apply_rope, positions=positions,
                         base=self.rope_base,
                         interleave=self.rope_interleave)
        q_nope, q_rope = q[..., :dn], rotate(q[..., dn:])
        k_rope = rotate(kv_a[..., None, rank:])            # (b, t, 1, dr)
        # the cached row: what a later step's scores and sums read
        rows = jnp.concatenate((c, k_rope[:, :, 0]), axis=-1)[:, :, None]
        new_cache = None
        if cache is None or (isinstance(pos, int) and pos == 0):
            # expanded, over this call's own rows only
            kv = product("btc,chn->bthn", c, w_kvb)
            k = jnp.concatenate(
                (kv[..., :dn], jnp.broadcast_to(k_rope, (b, t, h, dr))),
                axis=-1)
            o = _prompt_attention(
                self.attn_impl, jnp.concatenate((q_nope, q_rope), axis=-1),
                k, kv[..., dn:], MLA)
            if cache is not None:
                new_cache = kv_cache.write_latent_rows(cache, rows[:, :, 0],
                                                       pos)
        else:
            # absorbed: the engine's fused step over the pool's rows, or
            # generate()'s steps, a chunk, a resume over linear ones
            q_lat = product("bthn,chn->bthc", q_nope, w_kvb[..., :dn])
            o_lat, new_cache = kv_cache.decode_step(
                cache, jnp.concatenate((q_lat, q_rope), axis=-1), rows,
                rows[..., :rank], pos, live, name=f"attn_{MLA}_decode",
                scale=(dn + dr) ** -0.5)
            o = product("bthc,chv->bthv", o_lat, w_kvb[..., dn:])
        out = proj("attn_out", d_model)(o.reshape(b, t, h * dv))
        return out if new_cache is None else (out, new_cache)


class ShortConv(nn.Module):
    """A gated short convolution (the LFM2 line's ``conv`` operator), in
    an attention's place: ``[B ; C ; u] = x W_in`` (``d -> 3d``), ``g = B
    * u``, a causal depthwise filter of ``kernel`` taps over ``g``
    (``conv_t = sum_j w_j g_{t - (kernel - 1) + j}``, ``g`` nought before
    position 0, the last tap on the current position), the gate ``y = C *
    conv``, ``y W_out``. No heads, no positions, no softmax, no bias, no
    activation.

    What a later position needs of the earlier ones is the last ``kernel
    - 1`` inputs ``g``: the block's cache, whose step is
    :func:`mmlspark_tpu.ops.kv_cache.state_step` (linear rows for a
    prefill, a chunk and ``generate()``; the serving pool's constant-size
    state under the ``conv_decode`` kernel)."""

    kernel: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache=None, pos=None, decode=False, live=None):
        b, t, d = x.shape
        proj = nn.Dense(3 * d, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="in_proj")(
            x.astype(self.dtype))
        # (kernel, d): a tap's weights for every channel lie in the lanes
        taps = self.param("taps", nn.initializers.normal(0.02),
                          (self.kernel, d), self.param_dtype)
        entry = cache
        if cache is None:
            # no cache to fill: the same sum over rows that start empty
            entry, pos = kv_cache.StateRows(jnp.zeros((b, t, d),
                                                      self.dtype)), 0
        y, new_cache = kv_cache.state_step(entry, proj, taps, pos, live,
                                           name="conv_decode")
        out = nn.Dense(d, use_bias=False, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="out_proj")(y)
        return out if cache is None else (out, new_cache)


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
    shared_d_ff: int = 0    # an always-on, unweighted SwiGLU beside the sum
    scale: float = 1.0      # on every routing weight, after normalising
    score: str = "sigmoid"  # the router's kind (parallel/expert.router_topk)

    @nn.compact
    def __call__(self, x, valid=None):
        from mmlspark_tpu.parallel.expert import SIGMOID, moe_ffn_held

        d = x.shape[-1]
        router = self.param("router", nn.initializers.normal(0.02),
                            (d, self.n_experts), self.param_dtype)
        # a softmax router chooses by its scores alone: no selection bias
        bias = None if self.score != SIGMOID else self.param(
            "select_bias", nn.initializers.zeros, (self.n_experts,),
            self.param_dtype)
        w_gate, w_up, w_down = _Experts(self.held, d, self.d_ff,
                                        self.param_dtype, name="experts")()
        # the router reads the normed stream in float32, the experts in
        # the compute dtype
        out, counters = moe_ffn_held(
            x, router, bias, w_gate.astype(self.dtype),
            w_up.astype(self.dtype), w_down.astype(self.dtype),
            top_k=self.top_k, first=self.first, valid=valid,
            scale=self.scale, score=self.score,
        )
        if self.shared_d_ff:
            # every holder has the shared expert whole, for its own
            # tokens: it is no part of what expert parallelism divides
            out = out + _swiglu(x.astype(self.dtype), self.shared_d_ff,
                                "shared", self.dtype, self.param_dtype)
        return out, counters


def _swiglu(y, d_ff: int, prefix: str, dtype, param_dtype):
    """``(silu(y W_gate) * (y W_up)) W_out`` without biases, its three
    matrices named ``<prefix>_gate``, ``_up`` and ``_out`` in the calling
    module."""
    def dense(name, width):
        return nn.Dense(width, use_bias=False, dtype=dtype,
                        param_dtype=param_dtype, name=f"{prefix}_{name}")

    return dense("out", y.shape[-1])(
        nn.silu(dense("gate", d_ff)(y)) * dense("up", d_ff)(y))


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
    rope_interleave: bool = False
    # > 0: latent attention, ``rotary_dim`` the width of its one rotary
    # key and ``head_dim - rotary_dim`` that of a head's unrotated part
    kv_lora_rank: int = 0
    shared_d_ff: int = 0
    routed_scale: float = 1.0
    qk_norm: bool = False
    # > 0: no attention but a gated short convolution of that many taps
    # over a stream ``conv_width`` wide (what the state's rows are)
    conv_kernel: int = 0
    conv_width: int = 0
    block: int = 0           # > 0: block-causal (HybridAttention.block)
    router: str = "sigmoid"  # the routed FFN's router kind

    def cache_spec(self) -> tuple:
        """``(kind, rows, kv_heads, key width, value width)``: what the
        pool holds for this block. A full block keeps every position
        (``rows`` None: the pool's ``cache_len``), a window block a ring
        of ``window`` rows, a latent block every position's ONE row
        ``[c ; k_rope]``, whose first ``kv_lora_rank`` columns are the
        values, a convolution block a state of ``conv_kernel - 1`` rows
        the stream's width: no heads, and nothing for values."""
        if self.conv_kernel:
            return (kv_cache.STATE_ROWS, self.conv_kernel - 1, 1,
                    self.conv_width, 0)
        if self.kv_lora_rank:
            return (kv_cache.LATENT_ROWS, None, 1,
                    self.kv_lora_rank + self.rotary_dim, self.kv_lora_rank)
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
        if self.conv_kernel:
            attend = ShortConv(self.conv_kernel, self.dtype,
                               self.param_dtype, name="conv")
        elif self.kv_lora_rank:
            attend = LatentAttention(
                self.heads, self.head_dim - self.rotary_dim,
                self.rotary_dim, self.v_head_dim, self.kv_lora_rank,
                self.rope_base, self.rope_interleave, self.eps,
                self.attn_impl, self.dtype, self.param_dtype, name="attn")
        else:
            attend = HybridAttention(
                self.heads, self.kv_heads, self.head_dim, self.v_head_dim,
                self.window, self.rope_base, self.rotary_dim,
                self.value_scale, self.sink, self.attn_impl, self.dtype,
                self.param_dtype, self.rope_interleave, self.qk_norm,
                self.eps, block=self.block, name="attn")
        attn = attend(y, cache=cache, pos=pos, decode=decode, live=live)
        new_cache = None
        if cache is not None:
            attn, new_cache = attn
        x = x + attn
        y = RMSNorm(self.eps, self.param_dtype, name="ln2")(x)
        counters = None
        if self.routed:
            y, counters = RoutedFFN(
                self.n_experts, self.top_k, self.d_ff, self.held[0],
                self.held[1], self.dtype, self.param_dtype,
                self.shared_d_ff, self.routed_scale, score=self.router,
                name="moe",
            )(y, valid)
        else:
            y = _swiglu(y.astype(self.dtype), self.d_ff, "mlp", self.dtype,
                        self.param_dtype)
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
    rope_interleave: bool = False,
    kv_lora_rank: int = 0,
    qk_nope_head_dim: int = 0,
    qk_rope_head_dim: int = 0,
    shared_d_ff: int = 0,
    routed_scale: float = 1.0,
    qk_norm: bool = False,
    conv_kernel: int = 3,
    router: str = "sigmoid",
    block: int = 0,
    denoise_steps: int = 0,
    mask_id: int = -1,
) -> NamedGraph:
    """Causal decoder LM with a per-layer pattern: ``attention[i]`` in
    (``"full"``, ``"swa"``, ``"mla"``, ``"conv"``) and ``ffn[i]`` in
    (``"dense"``, ``"routed"``) give layer ``i`` its kinds. A ``conv``
    layer has no attention: a gated short convolution of ``conv_kernel``
    taps (:class:`ShortConv`) stands in its place. ``qk_norm`` puts an
    RMSNorm over every query and key head of the ``full`` and ``swa``
    layers, before the rotation. ``kv_heads``, ``rope_base``
    and ``full_sink`` are the full layers', ``swa_kv_heads``,
    ``swa_rope_base``, ``swa_sink`` and ``window`` the window layers'.
    The latent layers' are ``kv_lora_rank`` (the compressed row's width),
    ``qk_nope_head_dim`` and ``qk_rope_head_dim`` (a query head's
    unrotated and rotated parts, which add up to ``head_dim``) and
    ``rope_base``; ``rope_interleave`` pairs adjacent dimensions in every
    layer's rotation. ``held_experts = (first, count)`` says which of
    the router's ``n_experts`` experts this holder has (default: all);
    ``shared_d_ff`` > 0 gives every routed layer an always-on shared
    SwiGLU of that width, ``routed_scale`` multiplies the routing
    weights; ``router`` is ``"sigmoid"`` (with a selection bias) or
    ``"softmax"`` (over all experts, the chosen weights renormalised).

    ``block`` = L > 0 makes the stack one that GENERATES BY DIFFUSION
    OVER BLOCKS of L positions: every layer ``full`` and block-causal,
    ``denoise_steps`` (1..L) denoising steps a block, ``mask_id`` the
    token a position not yet committed reads (excluded from every
    choice). The serving engine then generates whole blocks
    (``models/generate.make_denoise_block``)."""
    attention, ffn = tuple(attention), tuple(ffn)
    if not attention or len(attention) != len(ffn):
        raise ParamError(
            f"attention ({len(attention)} layers) and ffn ({len(ffn)}) "
            "give every layer its kinds: same length, at least one"
        )
    for kind in attention:
        if kind not in (FULL, SWA, MLA, CONV):
            raise ParamError(
                f"attention kinds are '{FULL}', '{SWA}', '{MLA}' and "
                f"'{CONV}', got {kind!r}")
    if CONV in attention and int(conv_kernel) < 2:
        raise ParamError(
            f"'{CONV}' layers need conv_kernel >= 2 (the current position "
            f"and at least one before it), got {conv_kernel}")
    if MLA in attention and not (
            kv_lora_rank > 0 and qk_nope_head_dim > 0
            and qk_rope_head_dim > 0 and qk_rope_head_dim % 2 == 0
            and qk_nope_head_dim + qk_rope_head_dim == head_dim):
        raise ParamError(
            f"'{MLA}' layers need kv_lora_rank ({kv_lora_rank}) > 0 and a "
            f"query head of qk_nope_head_dim ({qk_nope_head_dim}) + an even "
            f"qk_rope_head_dim ({qk_rope_head_dim}) = head_dim ({head_dim})")
    if shared_d_ff < 0:
        raise ParamError(f"shared_d_ff must be >= 0, got {shared_d_ff}")
    if router not in ("sigmoid", "softmax"):
        raise ParamError(
            f"router must be 'sigmoid' or 'softmax', got {router!r}")
    if block and not (
            int(block) >= 1 and set(attention) == {FULL}
            and 1 <= int(denoise_steps) <= int(block)
            and 0 <= int(mask_id) < vocab_size):
        raise ParamError(
            f"block={block} generates by diffusion over blocks: every layer "
            f"'{FULL}' (got {sorted(set(attention))}), 1 <= denoise_steps "
            f"({denoise_steps}) <= block, and a mask_id ({mask_id}) inside "
            f"the vocabulary ({vocab_size})")
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
        swa, latent = a_kind == SWA, a_kind == MLA
        routed = f_kind == ROUTED_FFN
        blocks.append((f"block{i}", HybridBlock(
            heads=heads, kv_heads=swa_kv_heads if swa else kv_heads,
            head_dim=head_dim, v_head_dim=v_head_dim,
            window=int(window) if swa else None,
            rope_base=float(
                (swa_rope_base or rope_base) if swa else rope_base),
            rotary_dim=int(qk_rope_head_dim) if latent else rotary_dim,
            value_scale=float(value_scale),
            sink=bool(swa_sink if swa else full_sink and a_kind == FULL),
            ffn=f_kind,
            d_ff=expert_d_ff if routed else d_ff,
            n_experts=n_experts if routed else 0,
            top_k=top_k if routed else 0,
            held=(first, count) if routed else (0, 0),
            eps=norm_eps, attn_impl=attn_impl, param_dtype=dtype,
            rope_interleave=bool(rope_interleave),
            kv_lora_rank=int(kv_lora_rank) if latent else 0,
            shared_d_ff=int(shared_d_ff) if routed else 0,
            routed_scale=float(routed_scale) if routed else 1.0,
            qk_norm=bool(qk_norm) and a_kind in (FULL, SWA),
            conv_kernel=int(conv_kernel) if a_kind == CONV else 0,
            conv_width=int(d_model) if a_kind == CONV else 0,
            block=int(block),
            router=router,
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
            **({"block": int(block), "denoise_steps": int(denoise_steps),
                "mask_id": int(mask_id)} if block else {}),
        },
    )
