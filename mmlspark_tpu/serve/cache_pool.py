"""Slot-based KV-cache pool for the continuous-batching serving engine.

``models/generate.py`` preallocates one ``(B, total, hk, d)`` K/V buffer
pair per block PER CALL — correct for offline batch decode, wasteful for
serving, where requests arrive and retire continuously. The pool flips
the allocation: ONE buffer pair per block for the whole process —
head-major ``(S, hk, cache_len, d)`` on one device in bf16, linear
``(S, cache_len, hk, d)`` in int8 or under a mesh (head geometry from
:func:`mmlspark_tpu.models.generate.cache_specs`, the same fused-qkv
readout ``init_cache`` uses), where ``S`` is the number of serving slots.
A request leases a slot for its lifetime, the prefill writes its
prompt's K/V into positions ``[0, P)`` of that slot row, decode steps
append one position per tick, and retirement frees the slot for the next
request — no allocation, no reshape, no recompile anywhere in steady
state, which is what lets the scheduler's fused decode step stay a
single XLA program (the TensorFlow-style decoupled-worker dataflow,
arXiv:1605.08695, with fixed-shape device steps).

Stale K/V from a previous lease is harmless by construction: a new lease
always prefills ``[0, P)`` with ``P >= 1``, and the causal mask
(``q_offset = pos``) hides every position beyond the current request's
own write frontier.

With ``mesh`` set (docs/SERVING.md "Sharded serving") the pool is the
engine's device-placement anchor: every buffer is allocated COMMITTED
to a fixed :class:`~jax.sharding.NamedSharding` — the slot dim over the
``data`` axis, the KV-head dim over the ``model`` axis when it divides
evenly. ``write_prefill`` is one jitted program whose ``out_shardings``
are those same shardings, and the eager updates of ``free`` are
re-committed to them before the decode block sees them. That
fixed-point is what keeps the sharded engine's jitted programs at ONE
signature-cache entry per program family: the fused block's donated
inputs and ``out_shardings``-pinned outputs present byte-for-byte
identical shardings on every tick.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.models.generate import cache_specs
from mmlspark_tpu.ops.kv_cache import (
    FULL_ROWS,
    INDEXED_ROWS,
    LATENT_ROWS,
    LINEAR,
    RING_ROWS,
    STATE_ROWS,
    HeadMajorKV,
    IndexedRows,
    Int8Rows,
    LatentRows,
    SlotState,
    kv_head_scales,
    lane_pack,
    latent_width,
    quantize_kv,
    validate_kv_dtype,
)
from mmlspark_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def _put_rows(pool, values, slot, written):
    """``pool`` with the rows of ``slot`` that ``written`` marks taken
    from ``values`` (rows, hk, d), or (rows, W) of a latent pool, cast
    to the pool's dtype: a read of the slot's first rows, a select and
    one dynamic-update-slice, which XLA does in place on a donated
    pool."""
    at = (slot,) + (0,) * values.ndim
    old = jax.lax.dynamic_slice(pool, at, (1, *values.shape))
    values = jnp.where(written, values.astype(pool.dtype), old[0])
    return jax.lax.dynamic_update_slice(pool, values[None], at)


def _head_major_rows(kind: str, filled, rows: int, start, length):
    """One slot's rows of a head-major pool entry, ``(hk, rows, d)``,
    taken from a linear prefill cache ``filled`` (rows, hk, d), with the
    mask of the rows this write fills."""
    if kind == FULL_ROWS:
        rows = min(filled.shape[0], rows)
        at = jnp.arange(rows)
        taken = filled[:rows]
    else:
        # ring row j holds the latest position p < length with p % rows
        # == j; before the ring has wrapped, rows past the prompt's end
        # have no such position (p < 0) and stay as they are
        j = jnp.arange(rows)
        at = length - 1 - ((length - 1 - j) % rows)
        taken = jnp.take(filled, jnp.clip(at, 0, filled.shape[0] - 1),
                         axis=0)
    written = (at >= start) & (at >= 0) & (at < length)
    return jnp.moveaxis(taken, 0, 1), written[None, :, None]


def _state_rows(filled, rows: int, length):
    """One slot's row of a :class:`SlotState` entry, ``(1, rows * W)``: the
    last ``rows`` inputs below ``length`` of a linear state ``filled``
    (total, W), oldest first. A row that no position falls on (a prompt
    shorter than the filter's reach) is NOUGHT, whatever the slot held: a
    re-leased slot otherwise convolves its first tokens with the last
    occupant's inputs, and no length masks that as it masks an attention's
    stale rows."""
    at = length - rows + jnp.arange(rows)
    taken = jnp.take(filled, jnp.clip(at, 0, filled.shape[0] - 1), axis=0)
    return jnp.where((at >= 0)[:, None], taken, 0).reshape(1, -1)


def _write_slot(buffers, positions, live, prefill_cache, slot, start,
                length, *, kinds):
    """The whole of :meth:`SlotCachePool.write_prefill` as one program:
    rows ``[start, length)`` of ``slot`` take the batch-1
    ``prefill_cache``'s rows, every other row of the pool stays as it
    is, and the slot goes live at position ``length``. ``slot``,
    ``start`` and ``length`` are traced scalars, so the program is keyed
    by the prefill cache's shape alone. What an entry is, its type says
    (ops/kv_cache.py). An :class:`Int8Rows` entry's per-head scales are
    fixed here from the rows below ``length``. A :class:`HeadMajorKV`
    entry is ``full`` or a ``ring`` by ``kinds`` (static): a ``full`` one
    takes the same rows, transposed (adjacent heads side by side where
    the entry is packed); a ``ring`` of ``R`` rows takes, in row ``j``,
    the latest position below ``length`` that is congruent to ``j``: the
    prompt's last ``min(P, R)`` rows at ``pos % R``. A
    :class:`LatentRows` entry takes its one array's rows as they are. A
    :class:`SlotState` entry takes the last inputs below ``length``, the
    prompt's TRUE end and not its bucket's, whatever ``start`` is: the
    whole of a slot's state is rewritten at every admission."""
    new_buffers = {}
    for name, entry in buffers.items():
        if isinstance(entry, SlotState):
            filled = prefill_cache[name].rows[0]
            row = _state_rows(
                filled, entry.rows.shape[1] // filled.shape[1], length)
            new_buffers[name] = SlotState(jax.lax.dynamic_update_slice(
                entry.rows, row.astype(entry.rows.dtype), (slot, 0)))
            continue
        if isinstance(entry, (LatentRows, IndexedRows)):
            # the latent rows, and an indexed entry's index keys beside
            # them, row for row
            placed = []
            for pool, filled in zip(entry, prefill_cache[name]):
                filled = filled[0, :pool.shape[1]]
                row = jnp.arange(filled.shape[0])[:, None]
                placed.append(_put_rows(pool, filled, slot,
                                        (row >= start) & (row < length)))
            new_buffers[name] = type(entry)(*placed)
            continue
        if isinstance(entry, HeadMajorKV):
            placed = []
            for pool, filled in zip(entry, prefill_cache[name]):
                # a packed entry (lane_pack) takes adjacent heads side
                # by side: the same bytes, the head axis split
                filled = filled[0].reshape(
                    filled.shape[1], pool.shape[1], pool.shape[3])
                values, written = _head_major_rows(
                    kinds[name], filled, pool.shape[2], start, length)
                placed.append(_put_rows(pool, values, slot, written))
            new_buffers[name] = HeadMajorKV(*placed)
            continue
        rows = min(prefill_cache[name][0].shape[1], entry[0].shape[1])
        ck, cv = (c[0, :rows] for c in prefill_cache[name])
        row = jnp.arange(rows)[:, None, None]
        written = (row >= start) & (row < length)
        if not isinstance(entry, Int8Rows):
            new_buffers[name] = (_put_rows(entry[0], ck, slot, written),
                                 _put_rows(entry[1], cv, slot, written))
            continue
        # the prompt amax (+ margin) FIXES this lease's scales: decode
        # steps quantize against them in-graph, so they must be set
        # before the first block dispatch. The bucket's pad rows are
        # zeroed first, which leaves the amax that of the prompt alone
        ck, cv = (jnp.where(row < length, c, 0) for c in (ck, cv))
        k_scl = kv_head_scales(ck, axes=(0, 2))  # (hk,)
        v_scl = kv_head_scales(cv, axes=(0, 2))
        new_buffers[name] = Int8Rows(
            _put_rows(entry.k, quantize_kv(ck, k_scl), slot, written),
            _put_rows(entry.v, quantize_kv(cv, v_scl), slot, written),
            entry.k_scale.at[slot].set(k_scl),
            entry.v_scale.at[slot].set(v_scl),
        )
    # the slot's first decode step writes its first generated token's
    # K/V at position ``length`` (the prompt fills [0, P))
    return (new_buffers, positions.at[slot].set(length),
            live.at[slot].set(True))


class SlotCachePool:
    """Preallocated per-block K/V buffers with slot lease/free accounting.

    ``buffers`` is the live pytree the scheduler's jitted decode step
    reads and returns, ``{block: entry}``, each entry one of
    :mod:`mmlspark_tpu.ops.kv_cache`'s layouts (docs/SERVING.md "Cache
    entries"). The pool owns the host-side bookkeeping (which slots are
    leased); the arrays themselves stay on device and are replaced
    functionally each tick.

    On ONE device in bf16 every entry is typed: a block that DECLARES
    its geometry (``cache_spec()``, models/hybrid.py) gets what it
    declared, a ``HeadMajorKV`` of ``full`` rows or of a ``ring`` of its
    window's rows, ``latent`` rows (``LatentRows``: ONE array a
    block, ``(S, cache_len, W)``) or a convolution's ``state``
    (``SlotState``: ``(S, rows * W)``, constant in size whatever
    ``cache_len`` is); a block that declares nothing
    (``transformer_lm``) gets a ``HeadMajorKV`` of kind ``full``, ``rows
    = cache_len``. All live in this one pool, are written by the
    one jitted ``_write_slot`` and read by the one fused decode block.
    Under a mesh an undeclared block keeps LINEAR rows, a plain pair.

    ``kv_dtype="int8"`` (docs/PERFORMANCE.md "Quantized decode") stores
    K/V as int8 — HALF the bf16 pool's HBM bytes — in ``Int8Rows``
    entries: prefill fixes a slot's scales from its prompt amax (+
    headroom), decode steps quantize in-graph against them, and the
    flash-decode kernel dequantizes in-VMEM. All four leaves are
    DISTINCT arrays (donation) and carry pinned shardings under a mesh.
    The bf16 mode remains the accuracy oracle the int8 parity suite
    measures against.
    """

    def __init__(self, graph, variables, slots: int, cache_len: int, *,
                 mesh=None, kv_dtype: str = "bf16"):
        if slots < 1:
            raise FriendlyError(f"slots must be >= 1, got {slots}")
        if cache_len < 2:
            raise FriendlyError(
                f"cache_len must be >= 2 (one prompt token + one "
                f"generated), got {cache_len}"
            )
        specs = cache_specs(graph, variables)
        if not specs:
            raise FriendlyError(
                f"'{graph.name}' has no cache-accepting blocks; the "
                "serving engine needs the KV-cache decode path "
                "(transformer_lm family)"
            )
        declared = {name: spec[0] for name, spec in specs.items()
                    if spec[0] != LINEAR}
        kinds = ", ".join(f"'{k}'" for k in sorted(set(declared.values())))
        if declared and kv_dtype != "bf16":
            raise FriendlyError(
                f"'{graph.name}' declares its cache geometry (kinds {kinds}: "
                f"rings, latent rows, a convolution's state, keys and "
                f"values of different widths); kv_dtype={kv_dtype!r} rows "
                "are linear K/V rows of one width — serve it with "
                "kv_dtype='bf16'"
            )
        if declared and mesh is not None and mesh.size > 1:
            msize = int(mesh.shape.get(MODEL_AXIS, 1))
            uneven = [name for name, spec in specs.items()
                      if spec[2] % msize]
            raise FriendlyError(
                f"'{graph.name}' declares its cache geometry (kinds "
                f"{kinds}); its head-major full-length rows, rings, latent "
                "rows and convolution state are "
                "pooled on one device only — a mesh is not served yet"
                + (f" (and its '{MODEL_AXIS}' axis of {msize} does not "
                   f"divide the KV heads of {uneven[0]})" if uneven else "")
            )
        #: ``{block: kind}`` of the HEAD-MAJOR entries (full-length
        #: rows, or a ring): the declared ones, and on one device in
        #: bf16 every other block's too, as ``full``. The int8 rows and
        #: a mesh's pinned ``P(data, None, model, None)`` shardings keep
        #: linear rows
        self.kinds = dict(declared)
        if mesh is None and kv_dtype == "bf16":
            self.kinds = {name: declared.get(name, FULL_ROWS)
                          for name in specs}
        geometry = {name: (hk, dk)
                    for name, (_k, _r, hk, dk, *_) in specs.items()}
        self.mesh = mesh
        if mesh is not None:
            data = int(mesh.shape.get(DATA_AXIS, 1))
            if slots % data:
                raise FriendlyError(
                    f"slots ({slots}) must be a multiple of the mesh's "
                    f"'{DATA_AXIS}' axis ({data}): each device in the "
                    "data axis holds slots/data whole slot rows of "
                    "every K/V buffer. Round slots up (free slots are "
                    "natural pad rows — dead on device, zero decode "
                    "cost beyond the fixed shapes) or shrink the axis"
                )
        validate_kv_dtype(kv_dtype, geometry)
        self.kv_dtype = kv_dtype
        self.num_slots = slots
        self.cache_len = cache_len
        quantized = kv_dtype == "int8"
        store_dtype = jnp.int8 if quantized else jnp.bfloat16
        # device-placement anchors under a mesh; None on a single device
        self._slot_sharding = self._kv_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._slot_sharding = NamedSharding(mesh, P(DATA_AXIS))
            msize = int(mesh.shape.get(MODEL_AXIS, 1))
            self._kv_shardings = {}
        self.buffers = {}
        for name, (_kind, rows, hk, d, dv, *more) in specs.items():
            # K and V must be DISTINCT arrays: the engine's decode step
            # donates the whole buffer pytree (donate_argnums), and a
            # pair aliasing one allocation cannot be donated twice —
            # same for the int8 mode's two scale leaves
            kind = self.kinds.get(name, LINEAR)
            if kind == LATENT_ROWS:
                # one array a block, no head axis and no value array: the
                # decode kernel streams (rows, W) tiles of a slot
                entry = LatentRows(jnp.zeros(
                    (slots, cache_len, latent_width(d)), store_dtype))
            elif kind == INDEXED_ROWS:
                # the latent rows and, beside them, the indexer's one key
                # a position, which the index kernel streams (rows, Di)
                entry = IndexedRows(
                    jnp.zeros((slots, cache_len, latent_width(d)),
                              store_dtype),
                    jnp.zeros((slots, cache_len, more[0]), store_dtype))
            elif kind == STATE_ROWS:
                # a slot's ``rows`` inputs side by side, whatever
                # cache_len is: what the conv_decode kernel shifts in place
                entry = SlotState(jnp.zeros((slots, int(rows) * d),
                                            store_dtype))
            elif kind == LINEAR:
                entry = (jnp.zeros((slots, cache_len, hk, d), store_dtype),
                         jnp.zeros((slots, cache_len, hk, d), store_dtype))
                if quantized:
                    entry = Int8Rows(*entry,
                                     jnp.ones((slots, hk), jnp.float32),
                                     jnp.ones((slots, hk), jnp.float32))
            else:
                # head-major: the decode kernel streams (rows, d) tiles
                # of a KV head without a copy, rows packed to whole
                # lanes. A ring never needs more rows than the pool's
                # length
                rows = cache_len if kind == FULL_ROWS else min(
                    int(rows), cache_len)
                f = lane_pack(hk, d, dv)
                entry = HeadMajorKV(
                    jnp.zeros((slots, hk // f, rows, f * d), store_dtype),
                    jnp.zeros((slots, hk // f, rows, f * dv), store_dtype))
            if mesh is not None:
                # shard KV heads over the model axis only when they tile
                # evenly (GQA/MQA models with hk < model size replicate
                # the head dim, mirroring build_param_shardings' degrade);
                # the (slots, hk) scale leaves shard like the dims they
                # index. The entry's own type, so the trees match
                head = MODEL_AXIS if msize > 1 and hk % msize == 0 else None
                by_rank = {4: P(DATA_AXIS, None, head, None),
                           2: P(DATA_AXIS, head)}
                self._kv_shardings[name] = jax.tree_util.tree_map(
                    lambda a: NamedSharding(mesh, by_rank[a.ndim]), entry)
                entry = jax.device_put(entry, self._kv_shardings[name])
            self.buffers[name] = entry
        # LIFO free list popping the lowest id first keeps slot
        # assignment deterministic for the parity tests
        self._free = list(range(slots - 1, -1, -1))
        self._leased: set[int] = set()
        # deferred-free window (docs/SERVING.md "Async host loop"):
        # while the engine has a decode block IN FLIGHT that was
        # dispatched seeing this slot live, returning the slot to the
        # free list immediately would let the next admission re-lease
        # it and the in-flight block's masked writes would land in the
        # NEW tenant's row. The engine brackets each in-flight window
        # with defer_frees(gen)/flush_frees(gen): frees issued inside
        # the window reset the device row state immediately (those
        # updates are dependency-ordered AFTER the in-flight block's
        # outputs) but the free-list return waits until the stamped
        # generation's block has been fetched.
        self._defer_gen: int | None = None
        self._deferred: list[tuple[int, int]] = []
        self._deferred_slots: set[int] = set()
        # DEVICE-resident per-slot decode state, donated through the
        # engine's fused decode-block program alongside the K/V buffers
        # (docs/SERVING.md "Decode blocks"): each slot's next write
        # position and its live flag (True = active tenant). The scanned
        # micro-steps advance these ON DEVICE between host syncs; the
        # scheduler's host bookkeeping mirrors them deterministically.
        # Free-slot convention: (pos 0, dead) — a dead row runs through
        # the fixed-shape block masked out, writing only position-0
        # garbage that the slot's next prefill overwrites.
        self.positions = self._commit_slot(jnp.zeros((slots,), jnp.int32))
        self.live = self._commit_slot(jnp.zeros((slots,), bool))
        # write_prefill's program. ``buffers`` is donated, so each K/V
        # array is updated in place, as the fused decode block does it.
        # ``positions`` and ``live`` are not: the async host loop still
        # holds ``live`` as its in-flight block's fetch target when the
        # next tick admits, and S scalars gain nothing from an update
        # in place. Nor is the prefill cache: a chunked fill keeps it
        # as its carry and a hand-off ships it afterwards. Under a mesh
        # the outputs are pinned to the pool's own shardings, so the
        # decode block's donated inputs never change signature (the
        # compile-count pins depend on it)
        pinned = None
        if mesh is not None:
            pinned = (self._kv_shardings, self._slot_sharding,
                      self._slot_sharding)
        self._write = jax.jit(partial(_write_slot, kinds=dict(self.kinds)),
                              donate_argnums=(0,), out_shardings=pinned)

    # -- sharding anchors --------------------------------------------------

    def _commit_slot(self, arr):
        """Commit an (S,)-shaped per-slot array to the data axis (no-op
        without a mesh)."""
        if self._slot_sharding is None:
            return arr
        return jax.device_put(arr, self._slot_sharding)

    @property
    def kv_shardings(self):
        """``{block: (NamedSharding, NamedSharding)}`` matching
        ``buffers`` — what the engine pins the decode block's
        ``out_shardings`` to — or None without a mesh."""
        return self._kv_shardings

    @property
    def slot_sharding(self):
        """NamedSharding of the per-slot (S,) state (data axis), or
        None without a mesh."""
        return self._slot_sharding

    # -- accounting --------------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def leased_count(self) -> int:
        return len(self._leased)

    def leased_slots(self) -> list[int]:
        """Leased slot ids, ascending — what the engine's kill-parking
        walks to return every held slot deterministically."""
        return sorted(self._leased)

    @property
    def utilization(self) -> float:
        return len(self._leased) / self.num_slots

    def lease(self) -> int:
        if not self._free:
            raise FriendlyError(
                f"no free KV-cache slots (all {self.num_slots} leased); "
                "the scheduler should admit only into free slots — free "
                "a retired slot first or build the pool with more slots"
            )
        slot = self._free.pop()
        self._leased.add(slot)
        return slot

    def defer_frees(self, gen: int) -> None:
        """Open (or advance) a deferred-free window: until
        :meth:`flush_frees` passes ``gen``, freed slots reset their
        device row state immediately but stay OFF the free list — no
        new lease can collide with a decode block dispatched before
        the free (the async engine's zombie-row protection)."""
        self._defer_gen = gen

    def flush_frees(self, completed_gen: int | None = None) -> None:
        """Return every deferred slot whose stamped dispatch generation
        is ``<= completed_gen`` (all of them when None) to the free
        list, and close the window when None."""
        if completed_gen is None:
            self._defer_gen = None
        keep = []
        for gen, slot in self._deferred:
            if completed_gen is None or gen <= completed_gen:
                self._deferred_slots.discard(slot)
                self._leased.discard(slot)
                self._free.append(slot)
            else:
                keep.append((gen, slot))
        self._deferred = keep

    def free(self, slot: int) -> None:
        if slot not in self._leased or slot in self._deferred_slots:
            raise FriendlyError(
                f"slot {slot} is not leased (double free, or never "
                f"leased from this pool of {self.num_slots})"
            )
        if self._defer_gen is not None:
            self._deferred.append((self._defer_gen, slot))
            self._deferred_slots.add(slot)
        else:
            self._leased.remove(slot)
            self._free.append(slot)
        # restore the free-slot convention (pos 0, dead) so the fused
        # decode block keeps every write of this row inside the leased
        # region and its flash-decode length reads as zero
        self._commit_slot_pair(
            self.positions.at[slot].set(0),
            self.live.at[slot].set(False),
        )
        if self.kv_dtype == "int8":
            # release the slot's quantization-scale state back to the
            # 1.0 init: a freed (quarantined/preempted/retired) lease
            # must not leak its calibration into the next tenant, and
            # the parity tests assert the reset
            new_buffers = {
                name: entry._replace(
                    k_scale=entry.k_scale.at[slot].set(1.0),
                    v_scale=entry.v_scale.at[slot].set(1.0))
                for name, entry in self.buffers.items()
            }
            if self._kv_shardings is not None:
                new_buffers = jax.device_put(
                    new_buffers, self._kv_shardings
                )
            self.buffers = new_buffers

    def _commit_slot_pair(self, positions, live) -> None:
        """Rebind positions+live behind ONE pinned update — committing
        them separately would issue two eager dispatches per retire,
        and the retire path runs once per finished request."""
        if self._slot_sharding is not None:
            positions, live = jax.device_put(
                (positions, live),
                (self._slot_sharding, self._slot_sharding),
            )
        self.positions, self.live = positions, live

    # -- data path ---------------------------------------------------------

    def write_prefill(self, slot: int, prefill_cache: dict,
                      length: int, start: int = 0) -> tuple[int, int]:
        """Copy a batch-1 prefill cache (buffers holding valid K/V for
        positions ``[0, length)``) into positions ``[start, length)``
        of the slot's row — ``start=0`` is the classic full prefill;
        ``start>0`` resumes a partial fill whose prefix ``[0, start)``
        the slot already holds (same contract as the paged pool's
        ``write_prefill``, which prefix-cache resume uses).

        Returns ``(dispatches, bytes)``: the programs this write
        launched, counted beside the launch (1: the pool's one jitted
        write, which donates ``buffers`` and leaves ``prefill_cache``
        alone), and the K/V bytes it wrote into the pool. The engine
        stamps both on its ``serve.pool_write`` region."""
        if slot not in self._leased:
            raise FriendlyError(f"slot {slot} is not leased")
        if length > self.cache_len:
            raise FriendlyError(
                f"prefill length {length} exceeds the pool's cache_len "
                f"{self.cache_len}"
            )
        if not 0 <= start < max(length, 1):
            raise FriendlyError(
                f"write_prefill start {start} must lie in [0, length "
                f"{length})"
            )
        if self.kv_dtype == "int8" and start:
            # a lease's int8 scales are FIXED from its whole-prompt
            # amax before the first decode dispatch; a partial write
            # cannot re-derive them without dequantizing the resident
            # prefix, so the dense pool requires full writes
            raise FriendlyError(
                "dense int8 pools require start=0 writes: quantization "
                "scales are fixed per lease from the whole prompt "
                "(use the paged pool for resumable int8 fills)"
            )
        for name in self.buffers:
            # the first leaf of any entry: (1, rows, ...)
            rows = prefill_cache[name][0].shape[1]
            if rows < length:
                raise FriendlyError(
                    f"prefill cache of block '{name}' holds {rows} "
                    f"rows, fewer than the prefill length {length}"
                )
        nbytes = sum(self._write_bytes(length, start).values())
        # after the donation the old K/V arrays are gone: the pool's
        # state is rebound from the program's outputs and from nothing
        # else
        self.buffers, self.positions, self.live = self._write(
            self.buffers, self.positions, self.live, prefill_cache,
            np.int32(slot), np.int32(start), np.int32(length),
        )
        return 1, nbytes

    def _write_bytes(self, length: int, start: int) -> dict:
        """K/V bytes a write of rows ``[start, length)`` puts into the
        pool, by the kind of the entries they land in: a ring takes the
        last rows it has room for."""
        out = {LINEAR: 0, FULL_ROWS: 0, RING_ROWS: 0, LATENT_ROWS: 0,
               STATE_ROWS: 0, INDEXED_ROWS: 0}
        for name, entry in self.buffers.items():
            kind = self.kinds.get(name, LINEAR)
            if kind == INDEXED_ROWS:
                # the latent rows count as latent, the keys beside them as
                # the index's
                out[LATENT_ROWS] += ((length - start) * entry.rows.shape[2]
                                     * entry.rows.dtype.itemsize)
                out[kind] += ((length - start) * entry.keys.shape[2]
                              * entry.keys.dtype.itemsize)
                continue
            if kind == STATE_ROWS:
                # the slot's whole state, a row nought where the prompt is
                # shorter: the same bytes at every admission
                out[kind] += entry.rows.shape[1] * entry.rows.dtype.itemsize
                continue
            if kind == LATENT_ROWS:
                # the stored width, pad lanes and all: what the write moves
                out[kind] += ((length - start) * entry.rows.shape[2]
                              * entry.rows.dtype.itemsize)
                continue
            k, v = entry[:2]
            if kind == LINEAR:
                rows, per_row = length - start, 2 * math.prod(k.shape[2:])
            else:
                rows = min(length - start, k.shape[2])
                per_row = k.shape[1] * (k.shape[3] + v.shape[3])
            out[kind] += rows * per_row * k.dtype.itemsize
        return out

    def bytes_by_kind(self, length: int, start: int = 0) -> dict:
        """``{"bytes_full", "bytes_ring", "bytes_latent", "bytes_state"}``
        of a write of rows ``[start, length)``, for a pool that holds typed
        entries (and ``bytes_index``, the index keys, where it holds
        ``indexed`` ones); nothing for a pool of linear rows."""
        if not self.kinds:
            return {}
        by = self._write_bytes(length, start)
        out = {"bytes_full": by[FULL_ROWS], "bytes_ring": by[RING_ROWS],
               "bytes_latent": by[LATENT_ROWS],
               "bytes_state": by[STATE_ROWS]}
        if INDEXED_ROWS in self.kinds.values():
            out["bytes_index"] = by[INDEXED_ROWS]
        return out

    # -- accounting for telemetry ------------------------------------------

    def device_bytes_per_device(self) -> int:
        """KV-pool bytes resident PER DEVICE: each array's local shard
        size (``sharding.shard_shape``) times its itemsize, summed over
        every K/V buffer plus the per-slot position/live state. On a
        single device this is simply the pool's total footprint; under
        a mesh it is what each chip's HBM actually holds — the figure
        ``ServeMetrics.snapshot()`` reports as
        ``cache_pool_bytes_per_device``."""
        total = 0
        arrays = [a for pair in self.buffers.values() for a in pair]
        arrays += [self.positions, self.live]
        for arr in arrays:
            shard = arr.sharding.shard_shape(arr.shape)
            total += math.prod(shard) * arr.dtype.itemsize
        return int(total)
