"""Block allocator + paged KV pools + prefix cache — the serving
engine's memory layer.

Reference capability: vLLM-style paged KV management with hash-based
prefix caching (PAPERS.md "Ragged Paged Attention" describes the TPU
kernel shape this feeds).  The pool is ONE global
``(num_blocks, page, H_kv, D)`` k/v array pair per decoder layer;
requests address disjoint-or-shared block-id sets through per-request
block tables, so `max_batch` concurrent sequences share the HBM a dense
`(B, S_max, ...)` cache would burn on padding — and requests repeating
the same prompt prefix share the SAME physical blocks.

Block lifecycle (docs/SERVING.md has the diagram)::

    free ──allocate──▶ owned (ref 1) ──share──▶ shared (ref N)
      ▲                    │    ▲                   │
      │                    │    └──── CoW copy ◀────┘  (write to shared)
      │              free/deref
      │                    ▼
      └──evict(LRU)── cached (ref 0, registered, content intact)

Invariants (enforced here, relied on by the engine):

- every live block has refcount >= 1; ``free`` releases ONE reference —
  freeing an unknown id or a block with no outstanding references
  raises instead of silently corrupting the free list;
- a refcount-0 block REGISTERED in the prefix cache keeps its content
  and becomes evictable (LRU); eviction deregisters it before reuse;
- the engine reserves every block a request can ever WRITE at admission
  (cache-hit pages it will only read are borrowed via ``share``), so a
  running request never fails mid-decode on pool exhaustion;
- at drain (no waiting, no active requests) ``used_blocks == 0`` — all
  refcounts back to zero; cached blocks linger only as evictable
  capacity (checked by the `serving-smoke` CI gate).
"""

from __future__ import annotations

import collections
import hashlib
import json
import struct
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..resilience import _state as _rs_state

__all__ = ["BlockAllocator", "PagedKVCache", "PagedKVSpec", "PrefixCache",
           "SwapManager", "WindowSummarySpec", "cache_spec_of"]


class BlockAllocator:
    """Refcounted free-list allocation over block ids ``[0, num_blocks)``
    with an LRU pool of evictable (refcount-0, prefix-cached) blocks."""

    def __init__(self, num_blocks: int):
        if num_blocks <= 0:
            raise ValueError(f"num_blocks must be positive, got {num_blocks}")
        self.num_blocks = int(num_blocks)
        # pop() takes from the tail → low ids hand out first (stable
        # tests and readable block tables)
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._ref: Dict[int, int] = {}
        # refcount-0 blocks whose content the prefix cache still indexes,
        # in LRU order (oldest first) — reused only when the free list
        # runs dry, via on_evict so the cache drops its hash entry
        self._evictable: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._cached_key: Dict[int, object] = {}   # block → cache key
        self.on_evict: Optional[Callable[[int, object], None]] = None
        self.evictions = 0

    @property
    def free_blocks(self) -> int:
        """Immediately allocatable blocks (free list + evictable)."""
        return len(self._free) + len(self._evictable)

    @property
    def used_blocks(self) -> int:
        """Blocks with at least one outstanding reference."""
        return len(self._ref)

    @property
    def cached_blocks(self) -> int:
        """Refcount-0 blocks kept alive by the prefix cache (evictable)."""
        return len(self._evictable)

    def refcount(self, block_id: int) -> int:
        return self._ref.get(int(block_id), 0)

    def can_allocate(self, n: int) -> bool:
        return n <= self.free_blocks

    def allocate(self, n: int) -> List[int]:
        if n > self.free_blocks:
            raise RuntimeError(
                f"KV pool exhausted: asked for {n} blocks, "
                f"{self.free_blocks} free of {self.num_blocks} — admission "
                "should have gated this request (serving/scheduler.py)")
        ids = []
        for _ in range(n):
            if self._free:
                i = self._free.pop()
            else:
                # LRU eviction: oldest cached block loses its hash entry
                i, _ = self._evictable.popitem(last=False)
                key = self._cached_key.pop(i)
                self.evictions += 1
                if self.on_evict is not None:
                    self.on_evict(i, key)
            self._ref[i] = 1
            ids.append(i)
        return ids

    def share(self, block_id: int) -> None:
        """Take one more reference on a live or cached block (a prefix-
        cache hit borrowing the block into another request's table).
        Reviving a cached block removes it from the evictable pool but
        keeps its registration — future lookups still hit it."""
        i = int(block_id)
        if i in self._ref:
            self._ref[i] += 1
        elif i in self._evictable:
            del self._evictable[i]
            self._ref[i] = 1
        else:
            raise ValueError(
                f"share of block {i} which is neither live nor cached")

    def free(self, ids: Sequence[int]) -> None:
        """Release ONE reference per id.  A block reaching refcount 0
        returns to the free list — or, if the prefix cache registered
        it, to the evictable LRU pool with its content intact."""
        for i in ids:
            i = int(i)
            if not 0 <= i < self.num_blocks:
                raise ValueError(
                    f"free of unknown KV block {i} — valid ids are "
                    f"[0, {self.num_blocks})")
            if i not in self._ref:
                raise ValueError(
                    f"double free of KV block {i} — a request's block "
                    "list was reclaimed twice, or the id was never "
                    "allocated")
            self._ref[i] -= 1
            if self._ref[i] == 0:
                del self._ref[i]
                if i in self._cached_key:
                    self._evictable[i] = None       # MRU end
                else:
                    self._free.append(i)

    # -- prefix-cache bookkeeping (called by PrefixCache) ------------------

    def _mark_cached(self, block_id: int, key: object) -> None:
        self._cached_key[int(block_id)] = key

    def _is_cached(self, block_id: int) -> bool:
        return int(block_id) in self._cached_key


class PrefixCache:
    """Hash-based prefix cache: page-aligned prompt prefixes → pool
    blocks, with refcounted sharing and LRU eviction (the host half;
    copy-on-write copies run through
    :func:`incubate.nn.functional.paged_copy_blocks`).

    Keys are CHAINED content digests: page ``i``'s key is
    ``blake2b(key[i-1] || tokens[i*page:(i+1)*page])``, so a hit on page
    ``i`` implies every earlier token matches too — one dict probe per
    page, no collision risk at 16-byte digests.  Only FULL prompt pages
    are registered (a partial page's tail would diverge per request).
    """

    def __init__(self, allocator: BlockAllocator, page_size: int):
        self.allocator = allocator
        self.page_size = int(page_size)
        self._blocks: Dict[bytes, int] = {}     # key → block id
        self.hits = 0          # pages served from cache
        self.misses = 0        # hashable pages that missed
        allocator.on_evict = self._on_evict

    @staticmethod
    def page_keys(prompt_ids, page_size: int,
                  salt: bytes = b"") -> List[bytes]:
        """Chained digests for every FULL page of ``prompt_ids``.

        ``salt`` seeds the chain: pages written under different salts
        never share, however identical their tokens.  Multi-LoRA uses
        the adapter name here (scheduler.submit) — an adapter's q/k/v
        deltas change the KV CONTENT at every position, so a page
        prefilled under adapter A must never be borrowed by a request
        on adapter B (or the base model), and the chained digest is
        exactly the right place to encode that: one seed, every
        downstream page key diverges."""
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        keys, prev = [], bytes(salt)
        for p in range(ids.size // page_size):
            h = hashlib.blake2b(digest_size=16)
            h.update(prev)
            h.update(ids[p * page_size:(p + 1) * page_size].tobytes())
            prev = h.digest()
            keys.append(prev)
        return keys

    def lookup(self, keys: Sequence[bytes]) -> List[int]:
        """Block ids for the longest cached prefix of ``keys``.  Pure
        peek — the caller commits the hit with ``allocator.share`` per
        block plus one :meth:`record` call (admission is
        single-threaded, so peek-then-commit is atomic; a blocked
        admission retried every step must not inflate the stats)."""
        out: List[int] = []
        for k in keys:
            bid = self._blocks.get(k)
            if bid is None:
                break
            out.append(bid)
        return out

    def record(self, hits: int, misses: int) -> None:
        """Count one committed admission's page hits/misses."""
        self.hits += int(hits)
        self.misses += int(misses)

    def register(self, key: bytes, block_id: int) -> bool:
        """Index ``block_id`` (a fully-written prompt page owned by the
        caller) under ``key``.  First writer wins: if the key is already
        cached (two identical prompts prefilled concurrently), the
        duplicate block stays a normal private block."""
        if key in self._blocks:
            return False
        self._blocks[key] = int(block_id)
        self.allocator._mark_cached(int(block_id), key)
        return True

    def _on_evict(self, block_id: int, key: object) -> None:
        self._blocks.pop(key, None)

    def __len__(self) -> int:
        return len(self._blocks)

    def stats(self) -> Dict[str, float]:
        probes = self.hits + self.misses
        # "registered_pages" counts hash-indexed pages whether live or
        # evictable — deliberately NOT named like the serve.cached_blocks
        # gauge, which is the refcount-0 evictable pool only
        return {"hits": self.hits, "misses": self.misses,
                "hit_rate": (self.hits / probes) if probes else 0.0,
                "registered_pages": len(self._blocks),
                "evictions": self.allocator.evictions}


class PagedKVSpec:
    """The cache of full causal attention: ONE block table a request,
    whose pages only grow, ``ceil(positions / page)`` of them.  Every
    page a request can ever write is reserved at admission
    (``scheduler.py``), so growth and closing are no-ops here.

    A cache spec is what allocation, swap and accounting ask about a
    request's pages, whatever kind they are: how many a request of ``n``
    positions is reckoned at, which hold content (and in what order they
    travel to host and back), what a row of the step's table holds."""

    kind = "kv"
    reserves_ahead = True       # every page a request can write, at admission

    def __init__(self, page_size: int, table_width: int):
        self.page_size = int(page_size)
        self.table_width = int(table_width)
        self.swap_chunk = self.table_width

    def blocks_for(self, total_len: int) -> int:
        return -(-int(total_len) // self.page_size)

    def span_room(self, pos: int) -> Optional[int]:
        """Positions a span starting at ``pos`` may hold (None: any)."""
        return None

    def held_ids(self, st) -> List[int]:
        """The pages that hold ``st``'s content, in the order a swap
        payload lists them."""
        return [int(b) for b in st.table[:self.blocks_for(st.kv_len)]]

    def restore(self, st, allocator: BlockAllocator, oob: int) -> bool:
        """Private pages for a swapped request's whole budget (the host
        payload is scattered into the first of them); False when the
        pool cannot give them."""
        total = self.blocks_for(st.total_len)
        if not allocator.can_allocate(total):
            return False
        st.blocks = allocator.allocate(total)
        st.table = np.full((self.table_width,), oob, np.int32)
        st.table[:total] = st.blocks
        return True

    def table_row(self, st, start: int, oob: int) -> np.ndarray:
        return st.table

    def step_aux(self, plan, max_batch: int, chunk: int, oob: int):
        """Further inputs of the step that the cache kind needs (None)."""
        return None

    def aux_shapes(self, max_batch: int, chunk: int):
        return None


class WindowSummaryPages:
    """One request's pages under :class:`WindowSummarySpec`: the exact
    k/v pages of each window still held, by window, in position order,
    and the summary pages, in chunk order."""

    __slots__ = ("windows", "summaries")

    def __init__(self):
        self.windows: Dict[int, List[int]] = {}
        self.summaries: List[int] = []


class WindowSummarySpec:
    """The cache of EVA, chunked linearized attention
    (``models/evabyte.py``): TWO tables a request over pages of one
    geometry in one pool.

    - **window pages**: the exact k/v of the OPEN window, ``chunk``
      positions a page, at most ``window / chunk`` of them; allocated as
      the window fills and returned to the allocator when it closes (in
      the ``step_finish`` that consumed its last position);
    - **summary pages**: one row per completed chunk, ``chunk`` rows a
      page (so a page per ``chunk * chunk`` positions), growing for the
      request's life.  A summary is read by queries of the windows AFTER
      its own.

    Both kinds are the same bytes, so one pool under one allocator serves
    both: memory is not split ahead of the traffic, and swap moves pages
    without knowing their kind.  Nothing is reserved ahead: admission
    reckons a request of ``n`` positions at its peak,
    ``min(n, window) / chunk`` window pages + ``ceil(n / chunk^2)``
    summary pages, and compares it with what is free; pages are taken as
    positions are written (:meth:`short` / :meth:`grow`, before each
    step), and a pool that runs dry preempts its youngest request
    (``Engine._grow_pages``).

    A row of the step's table lists the summary pages that hold a row
    of a window before the span's, then the span's window's pages;
    positions along it (:meth:`cache_start`) are those pages' rows and
    then the offset in window ``w``, so that causal attention along the
    table, less the rows of the last summary page that belong to the
    span's own window, is EVA's
    (``incubate.nn.functional.eva_paged_attend``).
    """

    kind = "window+summary"
    reserves_ahead = False      # pages are taken as positions are written

    def __init__(self, window: int, chunk: int, max_seq_len: int):
        self.window, self.page_size = int(window), int(chunk)
        if self.window % self.page_size:
            raise ValueError(f"window={window} is no multiple of "
                             f"chunk={chunk}")
        c = self.page_size
        self.window_pages = self.window // c
        self.summary_pages = -(-int(max_seq_len) // (c * c))
        self.table_width = self.window_pages + self.summary_pages
        # a swap program gathers and scatters this many pages at once
        self.swap_chunk = min(self.table_width, 32)

    def blocks_for(self, total_len: int) -> int:
        n, c = int(total_len), self.page_size
        return -(-min(n, self.window) // c) + -(-n // (c * c))

    def span_room(self, pos: int) -> int:
        """A span ends with its window: a row's table holds one."""
        return self.window - pos % self.window

    def _seen(self, w: int):
        """(summary rows, summary pages) that window ``w``'s queries
        see: one row per chunk of the windows before it."""
        rows = self.window_pages * w
        return rows, -(-rows // self.page_size)

    def cache_start(self, start: int) -> int:
        """Where position ``start`` lies along its row's table: past the
        summary pages, at its offset in its window."""
        w = start // self.window
        return self._seen(w)[1] * self.page_size + start - w * self.window

    # -- what a request holds ---------------------------------------------

    def _held(self, kv_len: int):
        """(summary pages, open window, its pages) that hold content."""
        c = self.page_size
        w = kv_len // self.window
        return -(-(kv_len // c) // c), w, -(-(kv_len - w * self.window) // c)

    def held_ids(self, st) -> List[int]:
        n_sum, w, n_win = self._held(st.kv_len)
        return st.pages.summaries[:n_sum] \
            + st.pages.windows.get(w, [])[:n_win]

    def counts(self, st):
        """(window pages, summary pages) the request holds."""
        return (sum(len(p) for p in st.pages.windows.values()),
                len(st.pages.summaries))

    def _want(self, st, end: int):
        """Pages the request must hold before positions up to ``end``
        are written: {window: pages}, summary pages."""
        c = self.page_size
        want = {}
        for w in range(st.kv_len // self.window,
                       (end - 1) // self.window + 1):
            want[w] = -(-(min(end, (w + 1) * self.window)
                          - w * self.window) // c)
        return want, -(-(end // c) // c)

    def short(self, st, end: int) -> int:
        """Pages still to take before a step that writes up to ``end``."""
        want, n_sum = self._want(st, end)
        pg = st.pages
        return sum(max(0, n - len(pg.windows.get(w, [])))
                   for w, n in want.items()) \
            + max(0, n_sum - len(pg.summaries))

    def grow(self, st, end: int, allocator: BlockAllocator) -> int:
        """Take them (the caller has seen that the pool can give them)."""
        want, n_sum = self._want(st, end)
        pg = st.pages
        took = 0
        for w, n in want.items():
            have = pg.windows.setdefault(w, [])
            if n > len(have):
                new = allocator.allocate(n - len(have))
                have.extend(new)
                st.blocks.extend(new)
                took += len(new)
        if n_sum > len(pg.summaries):
            new = allocator.allocate(n_sum - len(pg.summaries))
            pg.summaries.extend(new)
            st.blocks.extend(new)
            took += len(new)
        return took

    def close(self, st, allocator: BlockAllocator):
        """Return the pages of every window that ``st.kv_len`` has left
        to the allocator.  (windows closed, pages freed)."""
        pg = st.pages
        closed = [w for w in pg.windows if (w + 1) * self.window <= st.kv_len]
        freed = 0
        for w in closed:
            ids = pg.windows.pop(w)
            allocator.free(ids)
            gone = set(ids)
            st.blocks = [b for b in st.blocks if b not in gone]
            freed += len(ids)
        return len(closed), freed

    def seat(self, st) -> None:
        st.pages = WindowSummaryPages()
        st.blocks = []
        st.table = None

    def restore(self, st, allocator: BlockAllocator, oob: int) -> bool:
        """Private pages for what the swapped request held, in
        :meth:`held_ids` order; False when the pool cannot give the
        request its peak (a restore that would be preempted again)."""
        if not allocator.can_allocate(self.blocks_for(st.total_len)):
            return False
        n_sum, w, n_win = self._held(st.kv_len)
        self.seat(st)
        st.pages.summaries = allocator.allocate(n_sum)
        if n_win:
            st.pages.windows[w] = allocator.allocate(n_win)
        st.blocks = self.held_ids(st)
        return True

    # -- what the step is given -------------------------------------------

    def table_row(self, st, start: int, oob: int) -> np.ndarray:
        w = start // self.window
        row = np.full((self.table_width,), oob, np.int32)
        n_sum = self._seen(w)[1]
        row[:n_sum] = st.pages.summaries[:n_sum]
        win = st.pages.windows[w]
        row[n_sum:n_sum + len(win)] = win
        return row

    def aux_shapes(self, max_batch: int, chunk: int):
        return {"cache_starts": ((max_batch,), np.int32),
                "summary_rows": ((max_batch,), np.int32),
                "summary_dst": ((max_batch, chunk // self.page_size),
                                np.int32)}

    def step_aux(self, plan, max_batch: int, chunk: int, oob: int):
        """``cache_starts`` ``(B,)``: each row's start along its table.
        ``summary_rows`` ``(B,)``: the summaries its queries see.
        ``summary_dst`` ``(B, chunk / page)``: for each chunk the row's
        span may complete, ``block * page + row`` of its summary's place,
        or the out-of-range block's where the span does not end it."""
        c = self.page_size
        starts = np.zeros((max_batch,), np.int32)
        rows = np.zeros((max_batch,), np.int32)
        dst = np.full((max_batch, chunk // c), oob * c, np.int32)
        for sp in plan:
            starts[sp.row] = self.cache_start(sp.start)
            rows[sp.row] = self._seen(sp.start // self.window)[0]
            summ = sp.st.pages.summaries
            for i in range(dst.shape[1]):
                j = sp.start // c + i
                if c * j + c - 1 < sp.start + sp.n:
                    dst[sp.row, i] = summ[j // c] * c + j % c
        return {"cache_starts": starts, "summary_rows": rows,
                "summary_dst": dst}


def cache_spec_of(model, page_size: int, max_seq_len: int):
    """The cache spec of a CausalLM: the kind the model declares
    (``kv_cache_spec()``), else the cache of full causal attention."""
    declared = getattr(model, "kv_cache_spec", None)
    if declared is None:
        return PagedKVSpec(page_size, -(-int(max_seq_len) // int(page_size)))
    d = declared()
    if d["kind"] != WindowSummarySpec.kind:
        raise NotImplementedError(f"unknown cache kind {d['kind']!r}")
    if int(page_size) != int(d["chunk"]):
        raise ValueError(
            f"page_size={page_size}: a {d['kind']} cache holds one chunk "
            f"a page, so page_size must be chunk_size={d['chunk']}")
    return WindowSummarySpec(d["window"], d["chunk"], max_seq_len)


class PagedKVCache:
    """Per-layer paged k/v pools + their allocator.

    ``caches`` is a list (one entry per decoder layer) of pool tuples in
    the :mod:`paddle_tpu.incubate.nn.functional` cache-arity convention:
    fp ``(k, v)`` of shape ``(num_blocks, page, H_kv, D)``, or — with
    ``dtype="int8"`` — quantized ``(k_i8, v_i8, k_scale, v_scale)`` with
    per-(slot, position, head) f32 scales, reusing the
    :func:`quantize_kv` formula the dense int8 caches use.  The engine
    donates the whole list through its compiled step and writes the
    returned buffers back here.

    ``mesh``: a serving mesh (``serving.distributed.serving_mesh``) puts
    every pool on the mesh with the KV-HEAD axis sharded over ``mp`` and
    the block axis replicated — block ids and tables stay mesh-invariant
    host integers, so the allocator, prefix cache, and CoW bookkeeping
    are untouched by sharding (docs/SERVING.md "Sharded serving").
    """

    def __init__(self, num_layers: int, num_blocks: int, page_size: int,
                 num_kv_heads: int, head_dim: int, dtype="float32",
                 mesh=None):
        if page_size <= 0:
            raise ValueError(f"page_size must be positive, got {page_size}")
        self.num_layers = int(num_layers)
        self.num_blocks = int(num_blocks)
        self.page_size = int(page_size)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.mesh = mesh
        if mesh is not None:
            # TP pool layout (docs/SERVING.md "Sharded serving"): the KV
            # HEAD axis is split over the mesh's mp axis — each shard
            # holds its heads' slice of EVERY block — while the block
            # axis stays replicated so block ids, tables, and the
            # allocator's host bookkeeping are mesh-invariant.
            if "mp" not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh must carry an 'mp' axis, got "
                    f"{mesh.axis_names} (serving.distributed.serving_mesh)")
            tp = mesh.shape["mp"]
            if self.num_kv_heads % tp:
                raise ValueError(
                    f"num_kv_heads={self.num_kv_heads} not divisible by "
                    f"the mesh's mp degree {tp} — the paged pools shard "
                    "the head axis")
        shape = (self.num_blocks, self.page_size, self.num_kv_heads,
                 self.head_dim)
        from ..models.generation import _is_int8
        self.quantized = _is_int8(dtype)
        if self.quantized:
            sshape = shape[:3]
            self.caches = [
                (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                 jnp.ones(sshape, jnp.float32), jnp.ones(sshape, jnp.float32))
                for _ in range(self.num_layers)]
        else:
            jdt = jnp.dtype(dtype)
            self.caches = [(jnp.zeros(shape, jdt), jnp.zeros(shape, jdt))
                           for _ in range(self.num_layers)]
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            # (num_blocks, page, H_kv[, D]) pools and int8 scale arrays:
            # head axis over mp, everything else replicated.  The spec
            # deliberately omits the trailing dim (jax normalizes output
            # specs that way) so the warmup dispatch and every
            # steady-state dispatch see IDENTICAL input shardings — a
            # trailing-None mismatch would add a second jit-cache entry
            # and break the one-executable contract the serving gates
            # check.
            sharding = NamedSharding(mesh, P(None, None, "mp"))
            self.caches = [tuple(jax.device_put(c, sharding)
                                 for c in layer) for layer in self.caches]
        self.allocator = BlockAllocator(self.num_blocks)

    @property
    def oob_block(self) -> int:
        """The out-of-range block-id sentinel: scatters to it DROP, so a
        table row full of it makes a slot's writes inert."""
        return self.num_blocks

    def nbytes(self) -> int:
        per_layer = sum(int(a.size) * a.dtype.itemsize
                        for a in self.caches[0])
        return per_layer * self.num_layers


class SwapManager:
    """Host-RAM swap space for preempted requests' KV pages.

    The preemption half of the front door (docs/SERVING.md "Front
    door"): instead of rejecting work when the pool is tight, the engine
    picks a victim, ``swap_out``s the content of its allocated pages —
    every layer's k/v rows, and for int8 pools the scale rows too — into
    host numpy buffers, frees the blocks, and later ``swap_in``s the
    bytes into freshly allocated blocks so the request resumes
    token-identical.

    Both directions run through ONE fixed-shape compiled program each (a
    ``(chunk,)``-row gather and a donated scatter), padded with the OOB
    sentinel: gather padding reads a clamped row the host copy discards,
    scatter padding drops (jax OOB-scatter semantics).  Any page count
    rides the same two executables — compiled once at
    ``Engine.warmup()``, zero recompiles under preemption churn (the
    ``chaos-serving`` gate's contract).

    Refcount discipline: swap only COPIES content — shared prefix-cache
    pages a victim borrowed are read, never mutated, so they are never
    swapped out from under the other slots (or cache entries) still
    referencing them; the victim merely drops its references and
    re-materializes private copies at restore.
    """

    def __init__(self, kv: PagedKVCache, chunk: int = 8):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.kv = kv
        self.chunk = int(chunk)
        self.pages_out = 0           # lifetime pages swapped to host
        self.pages_in = 0            # lifetime pages restored

        def gather(caches, ids):
            return [tuple(c[ids] for c in layer) for layer in caches]

        def scatter(caches, ids, payload):
            return [tuple(c.at[ids].set(p) for c, p in zip(layer, pl))
                    for layer, pl in zip(caches, payload)]

        self._gather = jax.jit(gather)
        # pools are donated, same as the engine's step/CoW programs: the
        # engine owns exactly one copy in HBM
        self._scatter = jax.jit(scatter, donate_argnums=(0,))

    def warmup(self) -> "SwapManager":
        """Compile both directions against all-OOB ids (gather rows are
        discarded, scatter rows drop) so preemption traffic compiles
        nothing."""
        ids = jnp.asarray(np.full((self.chunk,), self.kv.oob_block,
                                  np.int32))
        out = self._gather(self.kv.caches, ids)
        jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
        payload = [tuple(jnp.zeros((self.chunk,) + tuple(c.shape[1:]),
                                   c.dtype) for c in layer)
                   for layer in self.kv.caches]
        caches = self._scatter(self.kv.caches, ids, payload)
        jax.block_until_ready(jax.tree_util.tree_leaves(caches)[0])
        self.kv.caches = caches
        return self

    @staticmethod
    def payload_nbytes(host) -> int:
        return sum(int(a.nbytes) for layer in host for a in layer)

    @staticmethod
    def payload_to_bytes(host) -> bytes:
        """Frame a ``swap_out`` payload as one bytes blob: a
        length-prefixed JSON header (per-layer array dtypes + shapes —
        int8 pools carry four arrays per layer, the scale rows
        included) followed by each array's raw bytes in header order.
        This is the WIRE FORMAT the disaggregated KV transport ships
        between hosts (``serving/disagg.py``): ``payload_from_bytes``
        on any engine with the same pool geometry reconstructs a
        payload whose ``swap_in`` scatters byte-identical rows."""
        # dtype by NAME, not .str: custom dtypes (ml_dtypes bfloat16)
        # collapse to an anonymous void under .str ("<V2") and would
        # not round-trip; the registered name does.  Native byte order
        # assumed — the tier is homogeneous hosts.
        header = json.dumps(
            [[{"dtype": np.dtype(a.dtype).name, "shape": list(a.shape)}
              for a in layer] for layer in host]).encode()
        parts = [struct.pack("<I", len(header)), header]
        for layer in host:
            for a in layer:
                parts.append(np.ascontiguousarray(a).tobytes())
        return b"".join(parts)

    @staticmethod
    def payload_from_bytes(data: bytes):
        """Inverse of :meth:`payload_to_bytes`.  The returned arrays are
        read-only views over ``data`` (``swap_in`` only reads them) —
        copy before mutating."""
        (hlen,) = struct.unpack_from("<I", data, 0)
        metas = json.loads(data[4:4 + hlen].decode())
        host, off = [], 4 + hlen
        for layer in metas:
            rows = []
            for m in layer:
                dt = np.dtype(m["dtype"])
                n = int(np.prod(m["shape"])) if m["shape"] else 1
                a = np.frombuffer(data, dtype=dt, count=n,
                                  offset=off).reshape(m["shape"])
                off += n * dt.itemsize
                rows.append(a)
            host.append(tuple(rows))
        if off != len(data):
            raise ValueError(
                f"swap payload framing mismatch: header describes {off} "
                f"bytes, blob carries {len(data)}")
        return host

    def swap_out(self, block_ids: Sequence[int]):
        """Copy ``block_ids``'s rows from every layer's pools to host
        numpy; returns the payload ``swap_in`` takes.  Read-only on
        device state."""
        fi = _rs_state.FAULTS[0]
        if fi is not None:
            fi("serve.swap")
        n = len(block_ids)
        host = [tuple(np.empty((n,) + tuple(c.shape[1:]),
                               np.dtype(c.dtype)) for c in layer)
                for layer in self.kv.caches]
        for lo in range(0, n, self.chunk):
            m = min(self.chunk, n - lo)
            ids = np.full((self.chunk,), self.kv.oob_block, np.int32)
            ids[:m] = np.asarray(block_ids[lo:lo + m], np.int32)
            out = self._gather(self.kv.caches, jnp.asarray(ids))
            for layer, hlayer in zip(out, host):
                for arr, h in zip(layer, hlayer):
                    h[lo:lo + m] = np.asarray(arr)[:m]
        self.pages_out += n
        return host

    def swap_in(self, block_ids: Sequence[int], host) -> None:
        """Scatter a ``swap_out`` payload into ``block_ids`` (freshly
        allocated blocks) across every layer's pools."""
        fi = _rs_state.FAULTS[0]
        if fi is not None:
            fi("serve.swap")
        n = len(block_ids)
        for lo in range(0, n, self.chunk):
            m = min(self.chunk, n - lo)
            ids = np.full((self.chunk,), self.kv.oob_block, np.int32)
            ids[:m] = np.asarray(block_ids[lo:lo + m], np.int32)
            payload = []
            for hlayer in host:
                rows = []
                for h in hlayer:
                    r = h[lo:lo + m]
                    if m < self.chunk:     # pad: OOB rows drop anyway
                        full = np.zeros((self.chunk,) + r.shape[1:],
                                        r.dtype)
                        full[:m] = r
                        r = full
                    rows.append(jnp.asarray(r))
                payload.append(tuple(rows))
            self.kv.caches = self._scatter(self.kv.caches,
                                           jnp.asarray(ids), payload)
        self.pages_in += n
