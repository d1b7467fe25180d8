"""Paged KV-cache subsystem: memory virtualization for the slot pool.

``SlotCachePool`` (serve/cache_pool.py) reserves worst-case HBM: one
dense ``(slots, cache_len, hk, d)`` slab per block, every slot paying
for ``cache_len`` positions however short its request is, and identical
prompt prefixes (system prompts, few-shot headers) re-prefilled per
request. :class:`PagedCachePool` virtualizes that memory the way the
TensorFlow-runtime paper virtualizes worker state behind fixed-shape
dataflow steps (arXiv:1605.08695): the DEVICE arrays stay fixed-shape —
so every compiled serving program and its compile-count pins survive
unchanged — while a HOST-side allocator re-maps which physical pages
each slot's logical positions live in.

Layout per transformer block::

    K, V : (num_pages, hk, page_size, d)  bf16   physical page store
    PT   : (slots, max_pages)             int32  per-slot page table

``max_pages = cache_len // page_size``. A slot's logical position ``p``
lives at row ``PT[slot, p // page_size]``, offset ``p % page_size``.
The page store is HEADS-MAJOR (``(hk, page_size, d)`` per page, not the
slot pool's ``(cache_len, hk, d)``) so the paged decode kernel's
``(page_size, d)`` tiles sit on the TPU's sublane×lane axes
(docs/PERFORMANCE.md "Decode path"); ``page_size`` doubles as the
kernel's KV block, keeping the decode grid's shape — and its per-block
math, hence greedy-token parity with the dense pool — unchanged.

Host-side accounting:

- a per-data-shard FREE LIST with refcounts — a page is owned by one
  slot (refcount 1) or SHARED between slots and the prefix cache
  (refcount > 1). Pages allocate from the free list of the owning
  slot's data shard, so under a mesh every page a slot maps lives on
  the shard that already holds the slot's row of the page table
  (the PR 6 placement contract, now per page instead of per slot row).
- physical page ``s * pages_per_shard`` of each shard ``s`` is a
  reserved TRASH page, never allocated: a freed slot's page-table row
  points every entry at it, so the fused decode block's fixed-shape
  writes for dead rows land harmlessly in a page nothing ever reads
  (dead rows decode with live length 0).
- a PREFIX CACHE keyed on the prompt hash: a completed prefill
  registers its pages under its prompt, and a later prompt sharing a
  prefix maps those pages instead of recomputing them —
  COPY-ON-EXTEND, a slot privatizes a shared page only when its write
  frontier enters it (``refcount > 1`` at ``_ensure_writable`` time).
  Sharing is shard-local to keep the placement contract: a hit from a
  slot on another data shard copies the entry's pages onto the slot's
  shard instead of mapping them remotely (the prefill FLOPs are still
  saved). Page pressure evicts the PRESSURED SHARD's least-recently-
  used entries first (other shards' entries free nothing there and
  survive); if the free list is still empty the allocator raises the
  runtime's
  ``RESOURCE_EXHAUSTED`` spelling (:class:`~mmlspark_tpu.core.faults.
  ResourceExhausted`), which the engine's existing degradation ladder
  (PR 7) absorbs: smaller decode blocks, tighter admission, preemption
  at the floor — preempting a slot frees its pages, so pressure costs
  latency, not data.

Device-state discipline: host bookkeeping mutates eagerly BETWEEN
dispatches only. ``ServeEngine`` calls :meth:`ensure_decode_pages`
before each fused block so every page the block can write is mapped and
private up front; during the block the page tables are read-only, which
is what lets the block keep ONE host sync and the donation contract of
PR 5/6 (each transformer block carries its OWN device copy of the page
table — donation forbids aliased leaves).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

import jax
import jax.numpy as jnp

from mmlspark_tpu.core.exceptions import FriendlyError
from mmlspark_tpu.core.faults import ResourceExhausted
from mmlspark_tpu.models.generate import cache_geometry
from mmlspark_tpu.ops.kv_cache import (
    PagedInt8KV,
    PagedKV,
    kv_head_scales,
    quantize_kv,
    validate_kv_dtype,
)
from mmlspark_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

#: smallest page: the TPU sublane tile — a page's (page_size, d) face is
#: the paged decode kernel's KV block, and blocks under 8 rows cannot
#: tile
MIN_PAGE_SIZE = 8

#: bytes of TPU scalar memory the page table may take. The paged decode
#: kernel scalar-prefetches its shard's ``(slots, max_pages)`` int32 table
#: into one core's 1 MiB SMEM, where rows pad to 8 and columns to 128;
#: compiling for a described v5e accepts 1,015,808 padded bytes
#: (64 x 3968) and refuses 1,048,576 (tests/test_aot_tpu_compile.py)
PAGE_TABLE_SMEM_BYTES = (1 << 20) - (32 << 10)


def default_page_size(cache_len: int) -> int:
    """Smallest multiple of the sublane tile in [8, cache_len] dividing
    ``cache_len``: small pages maximize how much of the pool short
    requests leave free (the point of paging), the kernel's length
    clamp already prices the extra grid steps at zero for dead pages,
    and ``paged_flash_decode`` only tiles pages whose ``(page_size,
    d)`` face is whole sublanes. Raises at build time — not at the
    first decode dispatch — when ``cache_len`` admits no such page
    size."""
    for cand in range(MIN_PAGE_SIZE, cache_len + 1, MIN_PAGE_SIZE):
        if cache_len % cand == 0:
            return cand
    raise FriendlyError(
        f"cache_len ({cache_len}) has no page size that is a multiple "
        f"of {MIN_PAGE_SIZE} (the TPU sublane tile — the paged decode "
        "kernel's KV-block unit) and divides it evenly; round "
        f"cache_len to a multiple of {MIN_PAGE_SIZE} to serve paged"
    )


@dataclasses.dataclass
class _PrefixEntry:
    """One cached prompt prefill: the prompt that produced it, and the
    physical pages holding its K/V (refcounted — the entry itself holds
    one reference per page)."""

    prompt: np.ndarray          # (P,) int32
    length: int                 # P — positions [0, P) are valid
    pages: list[int]            # physical pages covering [0, P)
    last_used: int              # monotonic use counter (LRU eviction)


class PagedCachePool:
    """Drop-in replacement for ``SlotCachePool`` backed by paged
    storage. Same engine-facing surface (``lease``/``free``/
    ``write_prefill``/``buffers``/``positions``/``live``/
    ``kv_shardings``/``device_bytes_per_device``), plus the paging
    plane: :meth:`ensure_decode_pages`, the prefix-cache trio
    (:meth:`prefix_lookup` / :meth:`map_prefix` + :meth:`gather_prefix`
    / :meth:`prefix_insert`), :meth:`paging_stats`, :meth:`snapshot`.

    ``buffers`` is ``{block: PagedKV(k, v, page_table)}`` — the engine's
    decode jit donates and returns the whole pytree unchanged in
    structure, and ``ops/kv_cache.py`` reads the paged layout off the
    entry's type.

    ``kv_dtype="int8"`` (docs/PERFORMANCE.md "Quantized decode") stores
    the page faces as int8 — half the bf16 page store's HBM bytes, so a
    fixed page budget holds 2x the tokens — and each block's entry is a
    ``PagedInt8KV(k, v, page_table, k_scale, v_scale)`` with (num_pages,
    hk) f32 PER-PAGE scales as extra cache-pytree leaves: a page's scale
    is fixed at its FIRST write (prefill slice amax, or the first decode
    token's amax, + headroom), later writes into the page quantize
    against it, copy-on-extend copies it with the page, and
    ``paged_flash_decode`` dequantizes each fetched page in-VMEM.
    """

    def __init__(self, graph, variables, slots: int, cache_len: int, *,
                 mesh=None, page_size: int | None = None,
                 num_pages: int | None = None,
                 prefix_cache: bool = False, kv_dtype: str = "bf16"):
        if slots < 1:
            raise FriendlyError(f"slots must be >= 1, got {slots}")
        if cache_len < 2:
            raise FriendlyError(
                f"cache_len must be >= 2 (one prompt token + one "
                f"generated), got {cache_len}"
            )
        geometry = cache_geometry(graph, variables)
        if not geometry:
            raise FriendlyError(
                f"'{graph.name}' has no cache-accepting blocks; the "
                "serving engine needs the KV-cache decode path "
                "(transformer_lm family)"
            )
        if page_size is None:
            page_size = default_page_size(cache_len)
        if page_size < MIN_PAGE_SIZE:
            raise FriendlyError(
                f"page_size must be >= {MIN_PAGE_SIZE} (the TPU sublane "
                f"tile — it doubles as the paged decode kernel's KV "
                f"block), got {page_size}"
            )
        if page_size % MIN_PAGE_SIZE:
            raise FriendlyError(
                f"page_size ({page_size}) must be a multiple of "
                f"{MIN_PAGE_SIZE}: paged_flash_decode tiles each page's "
                "(page_size, d) face in whole TPU sublanes and rejects "
                "ragged pages at dispatch time"
            )
        if cache_len % page_size:
            raise FriendlyError(
                f"page_size ({page_size}) must divide cache_len "
                f"({cache_len}): a slot's logical positions tile into "
                "whole pages"
            )
        validate_kv_dtype(kv_dtype, geometry)
        self.kv_dtype = kv_dtype
        self.mesh = mesh
        data = 1
        if mesh is not None:
            data = int(mesh.shape.get(DATA_AXIS, 1))
            if slots % data:
                raise FriendlyError(
                    f"slots ({slots}) must be a multiple of the mesh's "
                    f"'{DATA_AXIS}' axis ({data}): each device in the "
                    "data axis holds slots/data whole page-table rows. "
                    "Round slots up or shrink the axis"
                )
        self.num_slots = slots
        self.cache_len = cache_len
        self.page_size = page_size
        self.max_pages = cache_len // page_size
        self._data = data
        self._slots_per_shard = slots // data
        # each data shard's kernel prefetches its own rows of the table
        table_bytes = (
            4 * -(-self._slots_per_shard // 8) * 8
            * -(-self.max_pages // 128) * 128
        )
        if table_bytes > PAGE_TABLE_SMEM_BYTES:
            raise FriendlyError(
                f"page table of {self._slots_per_shard} slots x "
                f"{self.max_pages} pages per data shard pads to "
                f"{table_bytes} bytes of TPU scalar memory; the paged "
                f"decode kernel stops compiling past "
                f"{PAGE_TABLE_SMEM_BYTES}. Use a larger page_size, fewer "
                "slots or a wider data axis"
            )
        if num_pages is None:
            # worst case: every slot fully paged, plus one trash page
            # per shard — a budget that can never exhaust. Callers size
            # it DOWN (bench.py serve_paged) to realize the memory win.
            num_pages = data * (self._slots_per_shard * self.max_pages + 1)
        if num_pages % data:
            raise FriendlyError(
                f"num_pages ({num_pages}) must be a multiple of the "
                f"'{DATA_AXIS}' axis ({data}): pages shard over it and "
                "each shard owns its own free list"
            )
        self.num_pages = num_pages
        self._pages_per_shard = num_pages // data
        if self._pages_per_shard < 2:
            raise FriendlyError(
                f"num_pages ({num_pages}) leaves "
                f"{self._pages_per_shard} page(s) per data shard; each "
                "shard needs its reserved trash page plus at least one "
                "allocatable page"
            )
        self.prefix_cache_enabled = bool(prefix_cache)

        quantized = kv_dtype == "int8"
        store_dtype = jnp.int8 if quantized else jnp.bfloat16
        # -- device-placement anchors (None on a single device) -------
        self._slot_sharding = self._kv_shardings = None
        self._pt_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            msize = int(mesh.shape.get(MODEL_AXIS, 1))
            self._slot_sharding = NamedSharding(mesh, P(DATA_AXIS))
            self._pt_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
            self._kv_shardings = {}
            for name, (hk, d) in geometry.items():
                head = (
                    MODEL_AXIS if msize > 1 and hk % msize == 0 else None
                )
                # pages replace slots on the data axis; the allocator
                # below keeps every page a slot maps on the slot's own
                # shard, so page reads/writes stay shard-local
                sh = NamedSharding(mesh, P(DATA_AXIS, head, None, None))
                if quantized:
                    # (num_pages, hk) scale leaves shard like the dims
                    # they index: pages over data, heads over model
                    ssc = NamedSharding(mesh, P(DATA_AXIS, head))
                    self._kv_shardings[name] = PagedInt8KV(
                        sh, sh, self._pt_sharding, ssc, ssc,
                    )
                else:
                    self._kv_shardings[name] = PagedKV(
                        sh, sh, self._pt_sharding)

        # -- host allocator state --------------------------------------
        # page table mirror: every entry starts at the owning shard's
        # trash page, so unmapped (and freed) rows absorb the fused
        # block's fixed-shape writes without touching a live page
        self._pt_host = np.empty((slots, self.max_pages), np.int32)
        for slot in range(slots):
            self._pt_host[slot, :] = self._trash_page(
                self._shard_of_slot(slot)
            )
        #: logical pages currently mapped per slot (contiguous [0, n))
        self._npages = [0] * slots
        self._refcount = np.zeros((num_pages,), np.int64)
        # LIFO free lists popping the lowest page id first (the slot
        # pool's determinism convention); trash pages never enter them
        self._free_pages: list[list[int]] = []
        for s in range(data):
            lo, hi = s * self._pages_per_shard, (s + 1) * self._pages_per_shard
            self._free_pages.append(list(range(hi - 1, lo, -1)))
        self._pt_dirty = False

        # -- prefix cache ----------------------------------------------
        #: prompt-hash -> entry (the dict key IS the prompt bytes; its
        #: hash is what the lookup structure indexes on)
        self._prefix: dict[bytes, _PrefixEntry] = {}
        self._use_counter = 0
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        self.prefix_evictions = 0
        self.cow_copies = 0
        #: cross-shard hits localized by page copy (mesh only)
        self.prefix_shard_copies = 0

        # -- device arrays ---------------------------------------------
        self.buffers = {}
        for name, (hk, d) in geometry.items():
            # K and V must be DISTINCT arrays (the engine donates the
            # pytree; one allocation cannot be donated twice) — and so
            # must each block's page-table copy, which is why PT rides
            # per block instead of as one shared array; the int8 mode's
            # two scale leaves follow the same rule
            k = jnp.zeros((num_pages, hk, page_size, d), store_dtype)
            v = jnp.zeros((num_pages, hk, page_size, d), store_dtype)
            pt = jnp.asarray(self._pt_host)
            entry = PagedKV(k, v, pt)
            if quantized:
                entry = PagedInt8KV(
                    k, v, pt,
                    jnp.ones((num_pages, hk), jnp.float32),
                    jnp.ones((num_pages, hk), jnp.float32),
                )
            if self._kv_shardings is not None:
                entry = jax.device_put(entry, self._kv_shardings[name])
            self.buffers[name] = entry
        self._free = list(range(slots - 1, -1, -1))
        self._leased: set[int] = set()
        # deferred-free window (same contract as SlotCachePool): while
        # the async engine has a decode block in flight, a freed slot's
        # device row state AND page-table row reset immediately (so the
        # NEXT dispatch writes to trash), but its free-list return and
        # page refcount release wait until the stamped generation's
        # block is fetched — the block already in flight writes through
        # the OLD device page table it was dispatched with, so those
        # pages must stay owned until its outputs materialize.
        self._defer_gen: int | None = None
        self._deferred: list[tuple[int, int, list[int]]] = []
        self._deferred_slots: set[int] = set()
        self.positions = self._commit_slot(jnp.zeros((slots,), jnp.int32))
        self.live = self._commit_slot(jnp.zeros((slots,), bool))

    # -- sharding anchors --------------------------------------------------

    def _commit_slot(self, arr):
        if self._slot_sharding is None:
            return arr
        return jax.device_put(arr, self._slot_sharding)

    @property
    def kv_shardings(self):
        """``{block: (K, V, PT) NamedShardings}`` matching ``buffers``
        (what the engine pins decode ``out_shardings`` to), or None
        without a mesh."""
        return self._kv_shardings

    @property
    def slot_sharding(self):
        return self._slot_sharding

    # -- shard geometry ----------------------------------------------------

    def _shard_of_slot(self, slot: int) -> int:
        return slot // self._slots_per_shard

    def _shard_of_page(self, page: int) -> int:
        return page // self._pages_per_shard

    def _trash_page(self, shard: int) -> int:
        return shard * self._pages_per_shard

    def _entry_shard(self, entry: _PrefixEntry) -> int:
        """The data shard holding ALL of an entry's pages:
        ``prefix_insert`` registers one slot's pages (allocated on that
        slot's shard) and ``map_prefix`` copies cross-shard pages local
        before a slot maps them, so an entry never straddles shards."""
        return self._shard_of_page(entry.pages[0])

    # -- page allocator ----------------------------------------------------

    def _alloc_page(self, shard: int) -> int:
        free = self._free_pages[shard]
        if not free:
            self._evict_prefix_entries(shard)
        if not free:
            in_use = self._pages_per_shard - 1
            raise ResourceExhausted(
                f"page allocator exhausted on data shard {shard}: all "
                f"{in_use} allocatable pages are mapped and the prefix "
                "cache has nothing left to evict"
            )
        page = free.pop()
        self._refcount[page] = 1
        return page

    def _decref(self, page: int) -> None:
        rc = int(self._refcount[page])
        if rc <= 0:
            raise FriendlyError(
                f"page {page} refcount underflow (double free: the page "
                "is not mapped by any slot or prefix entry)"
            )
        rc -= 1
        self._refcount[page] = rc
        if rc == 0:
            self._free_pages[self._shard_of_page(page)].append(page)

    def _evict_prefix_entries(self, shard: int) -> None:
        """Free-list pressure valve: drop least-recently-used prefix
        entries whose pages live ON ``shard`` until it has a free page
        (or no remaining entry can free one there). Entries on other
        shards are never touched — evicting them frees nothing on the
        pressured shard, so doing so would wipe unrelated shards'
        cached prefixes and still exhaust. Pages still mapped by active
        slots survive their entry's eviction — the refcount only
        reaches zero once the last slot frees too."""
        while not self._free_pages[shard]:
            local = [
                k for k, e in self._prefix.items()
                if self._entry_shard(e) == shard
            ]
            if not local:
                return
            key = min(local, key=lambda k: self._prefix[k].last_used)
            entry = self._prefix.pop(key)
            for page in entry.pages:
                self._decref(page)
            self.prefix_evictions += 1

    def _ensure_writable(self, slot: int, start: int, stop: int) -> bool:
        """Map — and privatize — the logical pages covering positions
        ``[start, stop)`` of ``slot``. Allocates unmapped pages from
        the slot's shard and COPY-ON-EXTENDs shared ones (refcount > 1:
        the slot's write frontier entered a prefix-cache page). Returns
        whether any K/V page content changed (a CoW copy happened).
        Raises :class:`ResourceExhausted` under page pressure; pages
        mapped before the failure stay accounted to the slot, so a
        later ``free``/preemption releases them."""
        if stop <= start:
            return False
        changed_kv = False
        first_pg = start // self.page_size
        last_pg = (stop - 1) // self.page_size
        shard = self._shard_of_slot(slot)
        for pg in range(min(self._npages[slot], first_pg), last_pg + 1):
            if pg >= self._npages[slot]:
                page = self._alloc_page(shard)
                self._pt_host[slot, pg] = page
                self._npages[slot] = pg + 1
                self._pt_dirty = True
            elif pg >= first_pg:
                phys = int(self._pt_host[slot, pg])
                if int(self._refcount[phys]) > 1:
                    # copy-on-extend: privatize before the write lands
                    page = self._alloc_page(shard)
                    self._copy_page(phys, page)
                    self._decref(phys)
                    self._pt_host[slot, pg] = page
                    self._pt_dirty = True
                    self.cow_copies += 1
                    changed_kv = True
        return changed_kv

    def _copy_page(self, src: int, dst: int) -> None:
        for name, entry in self.buffers.items():
            # int8 mode: a page copy is only faithful WITH its
            # quantization scales — the copied int8 values decode
            # through the same multipliers as the original's. Every
            # leaf but the table is indexed by page
            self.buffers[name] = entry._replace(**{
                field: leaf.at[dst].set(leaf[src])
                for field, leaf in entry._asdict().items()
                if field != "page_table"})

    # -- device-state commits ----------------------------------------------

    def _commit_pt(self) -> None:
        """Materialize the host page table onto the device — one
        DISTINCT array per block (donation forbids aliased leaves),
        committed to the table's canonical sharding under a mesh."""
        if not self._pt_dirty:
            return
        for name, entry in self.buffers.items():
            pt = jnp.asarray(self._pt_host)
            if self._kv_shardings is not None:
                pt = jax.device_put(pt, self._pt_sharding)
            self.buffers[name] = entry._replace(page_table=pt)
        self._pt_dirty = False

    def _commit_kv(self) -> None:
        """Re-commit every K/V page store to its canonical sharding
        after eager updates (no-op without a mesh: the functional
        ``.at`` updates already produced fresh arrays) — ONE pinned
        ``device_put`` of the whole pytree, mirroring the slot pool's
        batched update contract."""
        if self._kv_shardings is None:
            return
        # int8 mode: the (num_pages, hk) scale leaves ride the same
        # commit — eager page copies touch them too. A page table is
        # committed already (_commit_pt) and passes through untouched
        self.buffers = jax.device_put(self.buffers, self._kv_shardings)

    def _commit_slot_pair(self, positions, live) -> None:
        """Rebind positions+live behind ONE pinned update (two
        sequential device_puts would double the eager dispatch count on
        the retire/admit path)."""
        if self._slot_sharding is not None:
            positions, live = jax.device_put(
                (positions, live),
                (self._slot_sharding, self._slot_sharding),
            )
        self.positions, self.live = positions, live

    # -- accounting --------------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def leased_count(self) -> int:
        return len(self._leased)

    def leased_slots(self) -> list[int]:
        """Leased slot ids, ascending — what the engine's kill-parking
        walks to return every held slot (and its page mappings)
        deterministically."""
        return sorted(self._leased)

    @property
    def utilization(self) -> float:
        return len(self._leased) / self.num_slots

    @property
    def pages_free(self) -> int:
        return sum(len(f) for f in self._free_pages)

    @property
    def pages_allocatable(self) -> int:
        """Capacity net of the per-shard reserved trash pages."""
        return self.num_pages - self._data

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
        """Open (or advance) a deferred-free window — see
        :meth:`SlotCachePool.defer_frees`. The paged pool's split: the
        slot's PAGE-TABLE row points at the trash page IMMEDIATELY (so
        the next dispatch's dead-row writes are absorbed, exactly like
        a synchronous free), but the pages' refcounts only drop at
        :meth:`flush_frees` — the block already in flight writes
        through the OLD device table it was dispatched with, so its
        frontier page must stay owned until its outputs materialize."""
        self._defer_gen = gen

    def flush_frees(self, completed_gen: int | None = None) -> None:
        """Decref the held pages and return the slot for every deferred
        free whose stamped generation is ``<= completed_gen`` (all when
        None, which also closes the window)."""
        if completed_gen is None:
            self._defer_gen = None
        keep = []
        for gen, slot, pages in self._deferred:
            if completed_gen is None or gen <= completed_gen:
                self._deferred_slots.discard(slot)
                self._leased.discard(slot)
                self._free.append(slot)
                for pg in pages:
                    self._decref(pg)
            else:
                keep.append((gen, slot, pages))
        self._deferred = keep

    def free(self, slot: int) -> None:
        if slot not in self._leased or slot in self._deferred_slots:
            raise FriendlyError(
                f"slot {slot} is not leased (double free, or never "
                f"leased from this pool of {self.num_slots})"
            )
        if self._defer_gen is not None:
            # hold the refcounts, retarget the table: the deferred
            # entry keeps the page ids alive past the in-flight block,
            # while the trash-pointing row reaches every FUTURE
            # dispatch through the commit below
            pages = [
                int(self._pt_host[slot, pg])
                for pg in range(self._npages[slot])
            ]
            self._deferred.append((self._defer_gen, slot, pages))
            self._deferred_slots.add(slot)
            if self._npages[slot]:
                self._pt_host[slot, :] = self._trash_page(
                    self._shard_of_slot(slot)
                )
                self._npages[slot] = 0
                self._pt_dirty = True
        else:
            self._leased.remove(slot)
            self._free.append(slot)
            self._release_mappings(slot)
        self._commit_pt()
        self._commit_slot_pair(
            self.positions.at[slot].set(0),
            self.live.at[slot].set(False),
        )

    def _release_mappings(self, slot: int) -> None:
        """Unmap every logical page of ``slot``: decref (pages shared
        with the prefix cache or other slots survive; exclusive ones
        return to the free list) and point the row back at the trash
        page."""
        if not self._npages[slot]:
            return
        for pg in range(self._npages[slot]):
            self._decref(int(self._pt_host[slot, pg]))
        self._pt_host[slot, :] = self._trash_page(self._shard_of_slot(slot))
        self._npages[slot] = 0
        self._pt_dirty = True

    # -- data path ---------------------------------------------------------

    def write_prefill(self, slot: int, prefill_cache: dict, length: int,
                      start: int = 0) -> tuple[int, int]:
        """Scatter a batch-1 LINEAR cache's positions ``[start,
        length)`` into the slot's pages (allocating/privatizing them as
        needed) and mark the slot live at write frontier ``length``.
        ``start > 0`` is the prefix-cache resume path: positions
        ``[0, start)`` are already mapped to shared pages and only the
        remainder lands — the first write into a shared partial page is
        where copy-on-extend fires.

        Returns ``(dispatches, bytes)`` as the slot pool's
        ``write_prefill`` does: the array operations launched here,
        counted beside each launch (the page copies of a copy-on-extend
        are not among them), and the K/V bytes written."""
        if slot not in self._leased:
            raise FriendlyError(f"slot {slot} is not leased")
        if length > self.cache_len:
            raise FriendlyError(
                f"prefill length {length} exceeds the pool's cache_len "
                f"{self.cache_len}"
            )
        if not 0 <= start < length:
            raise FriendlyError(
                f"prefill start ({start}) must lie in [0, length="
                f"{length})"
            )
        self._ensure_writable(slot, start, length)
        pos = np.arange(start, length)
        pages = jnp.asarray(self._pt_host[slot, pos // self.page_size])
        offs = jnp.asarray(pos % self.page_size)
        dispatches, nbytes = 2, 0
        quantized = self.kv_dtype == "int8"
        for name, entry in self.buffers.items():
            pk, pv = entry.k, entry.v
            ck, cv = prefill_cache[name]
            hidx = jnp.arange(pk.shape[1])
            dispatches += 1
            # K and V alike: (num_pages, hk, page_size, d)
            nbytes += 2 * (length - start) * (
                pk.shape[1] * pk.shape[3] * pk.dtype.itemsize
            )
            if quantized:
                ks, vs = entry.k_scale, entry.v_scale
                # Per-page scales are fixed at each page's FIRST write:
                # a page is fresh here iff its first logical position
                # is at or past ``start`` — the prefix-resume path's
                # shared partial page keeps its registered scale (its
                # already-written half dequantizes through that
                # multiplier; re-deriving one would corrupt it), and
                # the remainder saturates into the budget instead.
                first_pg = start // self.page_size
                last_pg = (length - 1) // self.page_size
                k_rows, v_rows = [], []
                for pg in range(first_pg, last_pg + 1):
                    lo = max(pg * self.page_size, start)
                    hi = min((pg + 1) * self.page_size, length)
                    sk = ck[0, lo:hi].astype(jnp.float32)
                    sv = cv[0, lo:hi].astype(jnp.float32)
                    page = int(self._pt_host[slot, pg])
                    if pg * self.page_size >= start:
                        pks = kv_head_scales(sk, axes=(0, 2))
                        pvs = kv_head_scales(sv, axes=(0, 2))
                        ks = ks.at[page].set(pks)
                        vs = vs.at[page].set(pvs)
                    else:
                        pks, pvs = ks[page], vs[page]
                    k_rows.append(quantize_kv(sk, pks))
                    v_rows.append(quantize_kv(sv, pvs))
                    # a slice and a cast each; two scales and their
                    # scatters, or two reads; two quantizations
                    dispatches += 10 if pg * self.page_size >= start else 8
                qk = jnp.concatenate(k_rows, axis=0)
                qv = jnp.concatenate(v_rows, axis=0)
                nk = pk.at[
                    pages[:, None], hidx[None, :], offs[:, None]
                ].set(qk)
                nv = pv.at[
                    pages[:, None], hidx[None, :], offs[:, None]
                ].set(qv)
                self.buffers[name] = entry._replace(
                    k=nk, v=nv, k_scale=ks, v_scale=vs)
                dispatches += 4   # two concatenations, two scatters
            else:
                nk = pk.at[
                    pages[:, None], hidx[None, :], offs[:, None]
                ].set(ck[0, start:length].astype(pk.dtype))
                nv = pv.at[
                    pages[:, None], hidx[None, :], offs[:, None]
                ].set(cv[0, start:length].astype(pv.dtype))
                self.buffers[name] = entry._replace(k=nk, v=nv)
                # a slice and a scatter each, and a cast where it is one
                dispatches += 4 + (ck.dtype != pk.dtype) \
                    + (cv.dtype != pv.dtype)
        sharded = self._kv_shardings is not None
        if self._pt_dirty:   # one table a block, pinned under a mesh
            dispatches += len(self.buffers) * (1 + sharded)
        self._commit_kv()
        self._commit_pt()
        self._commit_slot_pair(
            self.positions.at[slot].set(length),
            self.live.at[slot].set(True),
        )
        dispatches += sharded + 2 + (self._slot_sharding is not None)
        return dispatches, nbytes

    def ensure_decode_pages(self, positions: dict[int, int],
                            t_block: int) -> None:
        """Pre-map every page the next fused decode block can write:
        slot ``s`` at frontier ``p`` writes positions ``[p, p +
        t_block)`` (clipped to ``cache_len``). Called by the engine
        BEFORE the dispatch — the page tables are read-only while the
        block runs, preserving its one-host-sync contract — and inside
        its fault envelope, so :class:`ResourceExhausted` here walks
        the same degradation ladder as a real allocator OOM."""
        changed_kv = False
        for slot, pos in positions.items():
            if slot in self._leased:
                stop = min(pos + t_block, self.cache_len)
                changed_kv |= self._ensure_writable(slot, pos, stop)
        if changed_kv:
            self._commit_kv()
        self._commit_pt()

    # -- prefix cache ------------------------------------------------------

    def prefix_lookup(self, seq, bucket_fn, slot: int | None = None):
        """Best reusable prefix for ``seq``: the cached entry sharing
        the longest common prefix, trimmed to ``keep`` positions such
        that (a) at least one remainder token is left to prefill (its
        logits seed decode), and (b) the remainder's padded bucket
        still fits the linear resume cache (``keep + bucket_fn(len -
        keep) <= cache_len`` — a clamped ``dynamic_update_slice`` would
        corrupt the shared prefix otherwise). With ``slot`` given,
        entries whose pages live on the slot's data shard win coverage
        ties — a same-shard hit maps shared pages for free where a
        cross-shard hit pays :meth:`map_prefix`'s localizing page
        copies. Returns ``(entry, keep)`` or None when nothing covers
        at least one page."""
        if not self._prefix:
            return None
        shard = None if slot is None else self._shard_of_slot(slot)
        seq = np.asarray(seq, np.int32)
        best, best_c, best_local = None, 0, False
        for entry in self._prefix.values():
            m = min(int(seq.size), entry.length)
            if m < best_c:
                continue
            neq = np.nonzero(seq[:m] != entry.prompt[:m])[0]
            c = int(neq[0]) if neq.size else m
            local = shard is None or self._entry_shard(entry) == shard
            if c > best_c or (
                c == best_c and c > 0 and local and not best_local
            ):
                best, best_c, best_local = entry, c, local
        keep = min(best_c, int(seq.size) - 1)
        while (
            keep >= self.page_size
            and keep + bucket_fn(int(seq.size) - keep) > self.cache_len
        ):
            keep -= 1
        if best is None or keep < self.page_size:
            return None
        return best, keep

    def map_prefix(self, slot: int, entry: _PrefixEntry,
                   keep: int) -> bool:
        """Map the entry's pages covering ``[0, keep)`` into ``slot``.
        Pages on the slot's data shard are SHARED (refcounts rise,
        nothing is copied — the prefix prefilled ONCE); pages on
        another shard are copied onto local pages first, preserving the
        per-page placement contract while still skipping the prefix's
        prefill FLOPs. Any mappings the slot already holds are released
        first, making a faulted admit's retry idempotent.

        Returns False — mapping nothing, leaving the slot's existing
        mappings untouched — when the entry is STALE: evicted since the
        lookup (a prior attempt's own page pressure can do that, and
        eviction drops the entry's page references). Mapping a stale
        entry could resurrect pages already on the free list — mapped
        and allocatable at once — so the caller must fall back to a
        full prefill instead. For a registered entry the entry's own
        references pin every page above zero through the re-map, so the
        release below can never free them."""
        if slot not in self._leased:
            raise FriendlyError(f"slot {slot} is not leased")
        if self._prefix.get(entry.prompt.tobytes()) is not entry:
            return False
        self._release_mappings(slot)
        shard = self._shard_of_slot(slot)
        n = -(-keep // self.page_size)  # ceil
        copied = False
        for i in range(n):
            phys = entry.pages[i]
            if self._shard_of_page(phys) == shard:
                self._refcount[phys] += 1
                self._pt_host[slot, i] = phys
            else:
                # localize: an allocator raise here leaves pages [0, i)
                # accounted to the slot (npages tracks the loop), so a
                # retry or free releases them
                page = self._alloc_page(shard)
                self._copy_page(phys, page)
                self._pt_host[slot, i] = page
                self.prefix_shard_copies += 1
                copied = True
            self._npages[slot] = i + 1
            self._pt_dirty = True
        self._use_counter += 1
        entry.last_used = self._use_counter
        self.prefix_hits += 1
        self.prefix_tokens_saved += keep
        if copied:
            self._commit_kv()
        self._commit_pt()
        return True

    def gather_prefix(self, entry: _PrefixEntry, keep: int) -> dict:
        """Linearize the entry's first ``keep`` positions into fresh
        ``(1, cache_len, hk, d)`` caches — the resume program's input
        (the transformer's scalar-pos prefill path wants a linear
        cache; the pool's paged layout is a decode-side format).
        Committed replicated under a mesh so the resume jit sees one
        fixed signature per remainder bucket."""
        n = -(-keep // self.page_size)
        idx = jnp.asarray(np.asarray(entry.pages[:n], np.int32))
        rep = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self.mesh, P())
        out = {}
        for name, entry in self.buffers.items():
            hk, d = entry.k.shape[1], entry.k.shape[3]
            scales = ((entry.k_scale, entry.v_scale)
                      if isinstance(entry, PagedInt8KV) else (None, None))
            lin = []
            for store, scl in zip((entry.k, entry.v), scales):
                g = store[idx]  # (n, hk, ps, d)
                dtype = store.dtype
                if scl is not None:
                    # int8 pages dequantize through their per-page
                    # scales into the bf16 linear cache the resume
                    # program expects (it re-quantizes on write-back)
                    g = g.astype(jnp.float32) * scl[idx][:, :, None, None]
                    dtype = jnp.bfloat16
                g = jnp.swapaxes(g, 1, 2)  # (n, ps, hk, d)
                g = g.reshape(n * self.page_size, hk, d)[:keep]
                arr = jnp.zeros((1, self.cache_len, hk, d), dtype)
                arr = arr.at[0, :keep].set(g.astype(dtype))
                if rep is not None:
                    arr = jax.device_put(arr, rep)
                lin.append(arr)
            out[name] = tuple(lin)
        return out

    def prefix_insert(self, slot: int, seq) -> None:
        """Register ``slot``'s freshly-prefilled pages under its
        prompt. The entry takes one reference per page, keeping the
        K/V alive after the slot retires; a prompt already cached (same
        hash key) is a no-op."""
        seq = np.asarray(seq, np.int32)
        if int(seq.size) < self.page_size:
            return  # can never satisfy a lookup's one-page minimum
        key = seq.tobytes()
        if key in self._prefix:
            return
        n = -(-int(seq.size) // self.page_size)
        pages = [int(self._pt_host[slot, i]) for i in range(n)]
        for page in pages:
            self._refcount[page] += 1
        self._use_counter += 1
        self._prefix[key] = _PrefixEntry(
            prompt=seq.copy(), length=int(seq.size), pages=pages,
            last_used=self._use_counter,
        )

    # -- accounting for telemetry ------------------------------------------

    def device_bytes_per_device(self) -> int:
        """Pool bytes resident PER DEVICE (page stores + page tables +
        per-slot state), shard-shape accounting as the slot pool — the
        figure ``cache_pool_bytes_per_device`` reports. Strictly below
        the dense pool's worst-case reservation whenever ``num_pages <
        slots * max_pages`` (pages not reserved are pages not
        allocated)."""
        total = 0
        arrays = [a for tup in self.buffers.values() for a in tup]
        arrays += [self.positions, self.live]
        for arr in arrays:
            shard = arr.sharding.shard_shape(arr.shape)
            total += math.prod(shard) * arr.dtype.itemsize
        return int(total)

    def paging_stats(self) -> dict:
        """The paging plane's metric keys (schema-gated in
        tools/check_metrics_schema.py)."""
        allocatable = self.pages_allocatable
        free = self.pages_free
        return {
            "page_size": int(self.page_size),
            "pages_total": int(self.num_pages),
            "pages_free": int(free),
            "page_utilization": (
                round((allocatable - free) / allocatable, 4)
                if allocatable else None
            ),
            "prefix_cache_hits_total": int(self.prefix_hits),
            "prefix_cache_entries": len(self._prefix),
            "cow_copies_total": int(self.cow_copies),
            "prefix_tokens_saved_total": int(self.prefix_tokens_saved),
        }

    def refcount_audit(self) -> tuple[int, int]:
        """``(refcount_total, mapped_references)`` — the allocator's
        conservation law. Every unit of refcount must be owned by
        exactly one mapping: a slot page-table entry (``npages`` per
        slot) or a prefix-cache entry's page list. The fleet tests
        assert the two are equal on every replica's pool across a
        hand-off, a failover, and a drain (docs/SERVING.md
        "Disaggregated fleet") — a leak here is silent HBM loss."""
        refcount_total = int(self._refcount.sum())
        mapped = sum(self._npages) + sum(
            len(e.pages) for e in self._prefix.values()
        )
        return refcount_total, int(mapped)

    def snapshot(self) -> dict:
        """JSON-able paging state: page tables, refcounts, prefix-cache
        entries. Informational in restore (the engine re-prefills every
        request bit-identically, rebuilding mappings from scratch) but
        it makes a crash dump auditable: refcount totals must equal
        mapped-page counts, which the round-trip test asserts."""
        return {
            "kv_dtype": self.kv_dtype,
            "page_size": int(self.page_size),
            "num_pages": int(self.num_pages),
            "max_pages": int(self.max_pages),
            "page_table": self._pt_host.tolist(),
            "npages": list(self._npages),
            "refcounts": [int(x) for x in self._refcount],
            "prefix_entries": [
                {
                    "prompt": e.prompt.tolist(),
                    "length": e.length,
                    "pages": list(e.pages),
                    "last_used": e.last_used,
                }
                for e in self._prefix.values()
            ],
            "prefix_cache_hits_total": int(self.prefix_hits),
            "prefix_tokens_saved_total": int(self.prefix_tokens_saved),
            "cow_copies_total": int(self.cow_copies),
        }
