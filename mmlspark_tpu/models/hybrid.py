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
import numpy as np

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
    # > 1: the residual's streams, each the embedding; ``select``: a
    # sparse stack's (stream, choice) pair, the choice empty
    streams: int = 1
    select: bool = False

    @nn.compact
    def __call__(self, ids, pos=None):
        # positions are rotary, inside attention; ``pos`` is accepted so
        # the cached forward can hand every block the same arguments
        tok = nn.Embed(self.vocab_size, self.d_model,
                       param_dtype=self.param_dtype, name="token")(ids)
        tok = tok.astype(jnp.float32)
        if self.streams > 1:
            tok = jnp.broadcast_to(tok[..., None, :],
                                   (*tok.shape[:-1], self.streams,
                                    self.d_model))
        if self.select:
            return tok, jnp.zeros((*ids.shape, 0), jnp.int32)
        return tok


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
    # > 0: the query's low rank (``q_a``, ``q_a_norm``, ``q_b``)
    q_lora_rank: int = 0
    # an elementwise sigmoid gate on every head's output, from the
    # layer's normed input, before ``attn_out``
    gate: bool = False
    sink: bool = False       # a learned logit per head in the denominator
    # > 0: DeepSeek sparse attention, each query reading the ``topk``
    # latent rows of highest index score alone (:class:`Indexer`, of
    # ``index_heads`` x ``index_dim``, where ``index_own``; else the
    # choice handed down from the layer above is read)
    index_topk: int = 0
    index_heads: int = 0
    index_dim: int = 0
    index_own: bool = False

    @nn.compact
    def __call__(self, x, cache=None, pos=None, decode=False, live=None,
                 sel=None):
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

        c_q = None
        if self.q_lora_rank:
            c_q = RMSNorm(self.eps, self.param_dtype, name="q_a_norm")(
                proj("q_a", self.q_lora_rank)(x)).astype(self.dtype)
            q = proj("q_b", h * (dn + dr))(c_q).reshape(b, t, h, dn + dr)
        else:
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
        sink = None
        if self.sink:
            sink = self.param("sink", nn.initializers.zeros, (h,),
                              self.param_dtype).astype(jnp.float32)
        if self.index_topk:
            o, new_cache, sel, count = self._sparse(
                x, c_q, q_nope, q_rope, rows[:, :, 0], w_kvb, cache, pos,
                live, sel, sink, positions, product)
            out = proj("attn_out", d_model)(
                self._gated(x, o, proj).reshape(b, t, h * dv))
            return out, new_cache, sel, count
        new_cache = None
        if cache is None or (isinstance(pos, int) and pos == 0):
            # expanded, over this call's own rows only
            kv = product("btc,chn->bthn", c, w_kvb)
            k = jnp.concatenate(
                (kv[..., :dn], jnp.broadcast_to(k_rope, (b, t, h, dr))),
                axis=-1)
            o = _prompt_attention(
                self.attn_impl, jnp.concatenate((q_nope, q_rope), axis=-1),
                k, kv[..., dn:], MLA, sink=sink)
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
                scale=(dn + dr) ** -0.5, sink=sink)
            o = product("bthc,chv->bthv", o_lat, w_kvb[..., dn:])
        if self.gate:
            o = self._gated(x, o, proj)
        out = proj("attn_out", d_model)(o.reshape(b, t, h * dv))
        return out if new_cache is None else (out, new_cache)

    def _gated(self, x, o, proj):
        """``o`` (B, T, H, dv) times ``sigmoid(x W_gate)`` element by
        element, where the layer has the gate."""
        if not self.gate:
            return o
        b, t, h, dv = o.shape
        g = jax.nn.sigmoid(proj("gate", h * dv)(x).astype(jnp.float32))
        return (o.astype(jnp.float32) * g.reshape(b, t, h, dv)).astype(
            self.dtype)

    def _sparse(self, x, c_q, q_nope, q_rope, rows, w_kvb, cache, pos, live,
                sel, sink, positions, product):
        """The sparse mode: each query reads the ``index_topk`` latent rows
        that its own indexer (``index_own``) or the layer above chose
        (``sel``), absorbed (:func:`mmlspark_tpu.ops.kv_cache.sparse_step`),
        whatever the call: without a cache this call's rows are the
        entry. Returns ``(o, new cache or None, sel, count)``."""
        b, t = x.shape[:2]
        dn, dr, rank = self.nope_dim, self.rope_dim, self.kv_lora_rank
        index = None
        if self.index_own:
            index = Indexer(self.index_heads, self.index_dim, dr,
                            self.rope_base, self.rope_interleave,
                            self.dtype, self.param_dtype, name="indexer")(
                x, c_q, positions)
        elif sel is None:
            raise ParamError(
                "a sparse latent layer without its own indexer reads the "
                "choice of the layer above it; none was handed down")
        entry, at = cache, pos
        if cache is None:
            width = kv_cache.latent_width(rank + dr)
            zeros = jnp.zeros((b, t, width), self.dtype)
            entry, at = (kv_cache.IndexedRows(
                zeros, jnp.zeros((b, t, self.index_dim), self.dtype))
                if self.index_own else kv_cache.LatentRows(zeros)), 0
        q_lat = product("bthn,chn->bthc", q_nope, w_kvb[..., :dn])
        o_lat, new, sel, count = kv_cache.sparse_step(
            entry, jnp.concatenate((q_lat, q_rope), axis=-1), rows, at, live,
            index=index, sel=None if self.index_own else sel,
            topk=self.index_topk, scale=(dn + dr) ** -0.5, values=rank,
            sink=sink)
        o = product("bthc,chv->bthv", o_lat, w_kvb[..., dn:])
        return o, None if cache is None else new, sel, count


class Indexer(nn.Module):
    """DeepSeek-V3.2's lightning indexer: ``heads`` queries of ``dim``
    from the query's latent ``c_q``, ONE key a position ``LayerNorm(x
    W_k)``, head weights ``x W_w / sqrt(heads * dim)``; rotary positions
    on the first ``rope_dim`` numbers of queries and key. Returns ``(q^I
    (B, T, heads, dim), w (B, T, heads) float32, k^I (B, T, dim))``, what
    :func:`mmlspark_tpu.ops.sparse_attention.index_select` scores."""

    heads: int
    dim: int
    rope_dim: int
    rope_base: float
    rope_interleave: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, c_q, positions):
        from mmlspark_tpu.ops.rope import apply_rope

        b, t, _ = x.shape

        def proj(name, width, inp):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)(inp)

        rotate = partial(apply_rope, positions=positions,
                         base=self.rope_base, rotary_dim=self.rope_dim,
                         interleave=self.rope_interleave)
        q = rotate(proj("wq", self.heads * self.dim, c_q).reshape(
            b, t, self.heads, self.dim))
        k = LayerNorm(1e-6, self.param_dtype, name="k_norm")(
            proj("wk", self.dim, x))
        k = rotate(k.astype(self.dtype)[:, :, None])[:, :, 0]
        w = proj("weights", self.heads, x).astype(jnp.float32) * (
            (self.heads * self.dim) ** -0.5)
        return q, w, k


class LayerNorm(nn.Module):
    """LayerNorm with a gain and a bias, in float32."""

    eps: float = 1e-6
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,),
                           self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (d,),
                          self.param_dtype)
        x = x.astype(jnp.float32)
        mean = x.mean(axis=-1, keepdims=True)
        var = jnp.square(x - mean).mean(axis=-1, keepdims=True)
        return ((x - mean) * jax.lax.rsqrt(var + self.eps)
                * scale.astype(jnp.float32) + bias.astype(jnp.float32))


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
    limit: float = 0.0      # > 0: every SwiGLU clamped (_swiglu)

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
            **({"limit": self.limit} if self.limit else {}),
        )
        if self.shared_d_ff:
            # every holder has the shared expert whole, for its own
            # tokens: it is no part of what expert parallelism divides
            out = out + _swiglu(x.astype(self.dtype), self.shared_d_ff,
                                "shared", self.dtype, self.param_dtype,
                                self.limit)
        return out, counters


def _swiglu(y, d_ff: int, prefix: str, dtype, param_dtype,
            limit: float = 0.0):
    """``(silu(y W_gate) * (y W_up)) W_out`` without biases, its three
    matrices named ``<prefix>_gate``, ``_up`` and ``_out`` in the calling
    module. ``limit`` > 0 clamps: ``silu(min(g, limit)) * clip(u,
    -limit, limit)``."""
    def dense(name, width):
        return nn.Dense(width, use_bias=False, dtype=dtype,
                        param_dtype=param_dtype, name=f"{prefix}_{name}")

    if not limit:
        return dense("out", y.shape[-1])(
            nn.silu(dense("gate", d_ff)(y)) * dense("up", d_ff)(y))
    gate = jnp.minimum(dense("gate", d_ff)(y), limit)
    up = jnp.clip(dense("up", d_ff)(y), -limit, limit)
    return dense("out", y.shape[-1])(nn.silu(gate) * up)


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
    # a latent layer's query low rank, output gate and sink
    # (LatentAttention's)
    q_lora_rank: int = 0
    gate: bool = False
    # DeepSeek sparse attention: (topk, index heads, index width, own
    # indexer) of a latent layer, () for none. A block of a stack with
    # sparse layers carries ``(stream, choice)`` from block to block
    index: tuple = ()
    # > 1: the residual is this many streams, mixed around each sublayer
    # (:class:`HyperMix`: ``(magnitude, eps)`` in ``hc``)
    hc_streams: int = 1
    hc: tuple = (2.0, 1e-6)
    swiglu_limit: float = 0.0    # > 0: every SwiGLU clamped (_swiglu)

    def cache_spec(self) -> tuple:
        """``(kind, rows, kv_heads, key width, value width)``: what the
        pool holds for this block. A full block keeps every position
        (``rows`` None: the pool's ``cache_len``), a window block a ring
        of ``window`` rows, a latent block every position's ONE row
        ``[c ; k_rope]``, whose first ``kv_lora_rank`` columns are the
        values, a convolution block a state of ``conv_kernel - 1`` rows
        the stream's width: no heads, and nothing for values. A latent
        block that runs its own indexer keeps ``indexed`` rows, and a
        sixth number: the width of the index key it keeps a position."""
        if self.conv_kernel:
            return (kv_cache.STATE_ROWS, self.conv_kernel - 1, 1,
                    self.conv_width, 0)
        if self.kv_lora_rank and self.index and self.index[3]:
            return (kv_cache.INDEXED_ROWS, None, 1,
                    self.kv_lora_rank + self.rotary_dim, self.kv_lora_rank,
                    self.index[2])
        if self.kv_lora_rank:
            return (kv_cache.LATENT_ROWS, None, 1,
                    self.kv_lora_rank + self.rotary_dim, self.kv_lora_rank)
        kind, rows = ((kv_cache.FULL_ROWS, None) if self.window is None
                      else (kv_cache.RING_ROWS, int(self.window)))
        return (kind, rows, self.kv_heads, self.head_dim, self.v_head_dim)

    @property
    def routed(self) -> bool:
        return self.ffn == ROUTED_FFN

    @property
    def sparse(self) -> bool:
        """Whether this block reads a chosen subset of its latent rows
        (DeepSeek sparse attention): its decode step then counts the rows
        it read and the live rows it chose them from."""
        return bool(self.index)

    @nn.compact
    def __call__(self, x, cache=None, pos=None, rolled=False,
                 decode=False, live=None, valid=None):
        """The block over the residual ``x``: ``(B, T, C)``, or ``(B, T,
        n, C)`` for ``n = hc_streams`` streams, or in a stack of sparse
        layers the pair ``(residual, choice)``: the rows the layer that
        scored last chose, ``(B, T, k)``, which a layer without its own
        indexer reads and hands on. With several streams each sublayer
        ``F`` runs on the streams' mix ``H_pre X`` and leaves ``H_res X +
        H_post^T F`` (:class:`HyperMix`), else ``X + F(X)``. Returns the
        residual, and with a cache the new entry and the counters: the
        routing's, and a sparse block's decode step adds ``rows_read``
        (the chosen rows read over live slots) and ``rows_live`` (the
        rows they were chosen from)."""
        if rolled:
            raise ParamError(
                "a hybrid block rolls no cache of its own: generate() "
                "keeps it linear, the serving pool keeps the ring"
            )
        sel = None
        if self.index:
            x, sel = x
        mix = partial(HyperMix, self.hc[0], self.hc[1], self.eps, self.dtype,
                      self.param_dtype)

        def sublayer(name, x, f):
            if self.hc_streams == 1:
                out, *rest = f(x)
                return x + out, rest
            pre, post, res = mix(name=name)(x)
            with jax.named_scope("hc_pre"):
                inp = jnp.einsum("...n,...nc->...c", pre, x)
            out, *rest = f(inp)
            return hc_mix(res, x, post, out), rest

        def attend(inp):
            y = RMSNorm(self.eps, self.param_dtype, name="ln1")(inp)
            kwargs = {"cache": cache, "pos": pos, "decode": decode,
                      "live": live}
            if self.conv_kernel:
                attn = ShortConv(self.conv_kernel, self.dtype,
                                 self.param_dtype, name="conv")
            elif self.kv_lora_rank:
                topk, heads, width, own = self.index or (0, 0, 0, False)
                attn = LatentAttention(
                    self.heads, self.head_dim - self.rotary_dim,
                    self.rotary_dim, self.v_head_dim, self.kv_lora_rank,
                    self.rope_base, self.rope_interleave, self.eps,
                    self.attn_impl, self.dtype, self.param_dtype,
                    q_lora_rank=self.q_lora_rank, gate=self.gate,
                    sink=self.sink, index_topk=topk, index_heads=heads,
                    index_dim=width, index_own=own, name="attn")
                if self.index:
                    return attn(y, sel=sel, **kwargs)
            else:
                attn = HybridAttention(
                    self.heads, self.kv_heads, self.head_dim,
                    self.v_head_dim, self.window, self.rope_base,
                    self.rotary_dim, self.value_scale, self.sink,
                    self.attn_impl, self.dtype, self.param_dtype,
                    self.rope_interleave, self.qk_norm, self.eps,
                    block=self.block, name="attn")
            got = attn(y, **kwargs)
            return got if cache is not None else (got,)

        def ffn(inp):
            return self._ffn(RMSNorm(self.eps, self.param_dtype,
                                     name="ln2")(inp), valid)

        x, got = sublayer("hc_attn", x, attend)
        new_cache, counters = (got or [None])[0], {}
        if self.index:
            new_cache, sel, count = got
            if decode and live is not None and jnp.ndim(pos):
                counters = {"rows_read": count[:, 0].sum(dtype=jnp.int32),
                            "rows_live": ((pos + 1) * live.astype(
                                jnp.int32)).sum(dtype=jnp.int32)}
        x, (routing,) = sublayer("hc_ffn", x, ffn)
        counters.update(routing or {})
        out = (x, sel) if self.index else x
        if cache is None:
            return out
        return (out, new_cache, counters) if counters else (out, new_cache)

    def _ffn(self, y, valid):
        """The block's FFN over its normed input, and the routing
        counters (None for a dense FFN)."""
        if self.routed:
            return RoutedFFN(
                self.n_experts, self.top_k, self.d_ff, self.held[0],
                self.held[1], self.dtype, self.param_dtype,
                self.shared_d_ff, self.routed_scale, score=self.router,
                limit=self.swiglu_limit, name="moe",
            )(y, valid)
        return _swiglu(y.astype(self.dtype), self.d_ff, "mlp", self.dtype,
                       self.param_dtype, self.swiglu_limit), None


class HyperMix(nn.Module):
    """The maps of a manifold-constrained hyper-connection (mHC) around
    one sublayer of a residual of ``n`` streams ``X`` (..., n, C): with
    ``x = RMSNorm(vec X)`` (no gain) and ``[p ; q ; r] = x phi``,
    ``H_pre = sigmoid(a_pre p + b_pre)`` (n), ``H_post = magnitude *
    sigmoid(a_post q + b_post)`` (n) and ``H_res = SinkhornKnopp(exp(a_res
    r + b_res))`` (n x n, ``iters`` alternations of row and column
    normalisation, ``eps`` in every division). Returns the three, float32;
    the product ``x phi`` in the compute dtype with float32 accumulation."""

    magnitude: float = 2.0
    eps: float = 1e-6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    iters: int = 20

    @nn.compact
    def __call__(self, x):
        n, c = x.shape[-2:]
        width = 2 * n + n * n
        phi = self.param("phi", nn.initializers.normal(0.02), (n * c, width),
                         self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (width,),
                          self.param_dtype).astype(jnp.float32)
        alpha = self.param("alpha", nn.initializers.constant(0.1), (3,),
                           self.param_dtype).astype(jnp.float32)
        with jax.named_scope("hc_maps"):
            flat = x.reshape(*x.shape[:-2], n * c).astype(jnp.float32)
            flat = flat * jax.lax.rsqrt(
                jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
                + self.norm_eps)
            z = jnp.dot(flat.astype(self.dtype), phi.astype(self.dtype),
                        preferred_element_type=jnp.float32)
            pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
            post = self.magnitude * jax.nn.sigmoid(
                alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
            res = jnp.exp(alpha[2] * z[..., 2 * n:] + bias[2 * n:])
            res = res.reshape(*res.shape[:-1], n, n)
            for _ in range(self.iters):
                res = res / (res.sum(-1, keepdims=True) + self.eps)
                res = res / (res.sum(-2, keepdims=True) + self.eps)
        return pre, post, res


def hc_mix(res, x, post, out):
    """``H_res X + H_post^T F`` for the streams ``x`` (..., n, C) float32,
    ``res`` (..., n, n), ``post`` (..., n) and a sublayer's output ``out``
    (..., C): by the ``hc_mix`` kernel (:func:`mmlspark_tpu.ops.
    stream_mix.stream_mix`)."""
    from mmlspark_tpu.ops.stream_mix import stream_mix

    lead, (n, c) = x.shape[:-2], x.shape[-2:]
    rows = int(np.prod(lead))
    new = stream_mix(res.reshape(rows, n, n), x.reshape(rows, n, c),
                     post.reshape(rows, n), out.reshape(rows, c))
    return new.reshape(x.shape)


class HybridHead(nn.Module):
    vocab_size: int
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # a stack of several streams sums them before the norm; a sparse
    # stack hands the head its (stream, choice) pair
    streams: int = 1
    select: bool = False
    fp32: bool = False       # the head's matrix and product in float32

    @nn.compact
    def __call__(self, x):
        if self.select:
            x = x[0]
        if self.streams > 1:
            x = x.sum(axis=-2)
        x = RMSNorm(self.eps, self.param_dtype, name="ln_f")(x)
        if self.fp32:
            return nn.Dense(self.vocab_size, use_bias=False,
                            dtype=jnp.float32, param_dtype=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST,
                            name="head")(x)
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
    q_lora_rank: int = 0,
    attn_gate: bool = False,
    latent_sink: bool = False,
    index_topk: int = 0,
    index_heads: int = 0,
    index_dim: int = 0,
    indexer: tuple | None = None,
    hc_streams: int = 1,
    hc_magnitude: float = 2.0,
    hc_eps: float = 1e-6,
    swiglu_limit: float = 0.0,
    head_fp32: bool = False,
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
    (``models/generate.make_denoise_block``).

    The latent layers' further options: ``q_lora_rank`` > 0 gives the
    query a low rank (``q_a``, an RMSNorm, ``q_b``), ``attn_gate`` an
    elementwise sigmoid gate on every head's output (from the layer's
    normed input), ``latent_sink`` a learned logit per head in the
    softmax's denominator. ``index_topk`` > 0 makes every latent layer
    DEEPSEEK-SPARSE: a query reads the ``index_topk`` latent rows of
    highest index score alone; ``indexer[i]`` is ``"full"`` where layer
    ``i`` runs its own lightning indexer (``index_heads`` heads of
    ``index_dim``, keys kept in the cache beside the latent rows) and
    ``"shared"`` where it reads the choice of the nearest ``full`` layer
    above it. ``hc_streams`` = n > 1 makes the residual n streams, the
    embedding copied into each and summed before the final norm, mixed
    around every sublayer by manifold-constrained hyper-connections
    (:class:`HyperMix`; ``hc_magnitude`` the post map's scale, ``hc_eps``
    in the Sinkhorn divisions); both take latent layers only.
    ``swiglu_limit`` > 0 clamps every SwiGLU (``silu(min(g, limit)) *
    clip(u, -limit, limit)``), ``head_fp32`` keeps the head's matrix and
    product in float32."""
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
    index = tuple(indexer or ())
    if index_topk and not (
            set(attention) == {MLA} and len(index) == len(attention)
            and set(index) <= {FULL, "shared"} and index[0] == FULL
            and index_heads > 0 and index_dim > 0
            and 0 < int(qk_rope_head_dim) < index_dim):
        raise ParamError(
            f"index_topk={index_topk} makes every layer a sparse '{MLA}' "
            f"one: got kinds {sorted(set(attention))}, indexer {index} "
            "(one 'full' or 'shared' a layer, the first 'full'), "
            f"index_heads {index_heads}, index_dim {index_dim} (wider "
            f"than the rotary part, {qk_rope_head_dim})")
    if int(hc_streams) > 1 and set(attention) != {MLA}:
        raise ParamError(
            f"hc_streams={hc_streams} mixes the streams of latent "
            f"('{MLA}') layers only, got {sorted(set(attention))}")
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
    streams, sparse = max(int(hc_streams), 1), bool(index_topk)
    blocks: list[tuple[str, Any]] = [
        ("embed", TokenEmbed(vocab_size, d_model, dtype, streams, sparse))
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
            sink=bool(latent_sink if latent else swa_sink if swa
                      else full_sink and a_kind == FULL),
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
            q_lora_rank=int(q_lora_rank) if latent else 0,
            gate=bool(attn_gate) and latent,
            index=((int(index_topk), int(index_heads), int(index_dim),
                    index[i] == FULL) if sparse else ()),
            hc_streams=streams,
            hc=(float(hc_magnitude), float(hc_eps)),
            swiglu_limit=float(swiglu_limit),
        )))
    blocks.append((FINAL_NODE, HybridHead(vocab_size, norm_eps,
                                          param_dtype=dtype,
                                          streams=streams, select=sparse,
                                          fp32=bool(head_fp32))))
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
