"""Paged KV cache — fixed-size blocks in a preallocated device pool.

Reference capability: the block-table KV layout of
``block_multi_head_attention`` (paddle/phi/kernels/fusion/gpu) and vLLM's
PagedAttention; TPU-native shape per Ragged Paged Attention
(arxiv 2604.15464): per-layer pools ``[num_pages, page_size, H, Dh]``, a
per-request **block table** of physical page ids, and a host-side
free-list allocator. This replaces the dense ``[B, T, H, Dh]`` buffers of
``models/gpt.py``'s compiled decode for serving: memory is bounded by
*tokens actually cached* (rounded up to one page), not by
``batch × max_seq_len``, so slots with short requests don't reserve the
worst case and the continuous-batching scheduler can admit until the pool
— not the batch shape — is full.

A model's layers are grouped by what they keep for a token and how far
back (:class:`PageGroup`): each group has its own allocator, page count
and block table a request, so that layers behind a sliding window give
their pages back while full layers keep every token.

A layer may instead keep ONE state of a fixed size a request
(``LayerState.per_request``: the recurrent state of a linear-attention
layer): its pools are ``[max_slots + 1, ...]``, held by the request's
decode SLOT, with no block table, no growth and no window; such layers
belong to no page group and take no pages. Pool index 0 is the **scrap
slot** (a request in slot ``s`` owns index ``s + 1``): padded rows of a
round read and write it.

Physical page 0 of every group is reserved as the **scrap page**: padded
block-table entries, the entries of pages a windowed group gave back and a
round's pad tokens point at it, so masked lanes of the
round have a legal write/read target without branching. All pool updates
are functional (``.at[].set``) so the round can be one jitted XLA program
with donated pool buffers.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from ..core.dispatch import apply

__all__ = ["BlockAllocator", "PagedKVCache", "PageGroup", "LayerState",
           "kv_state", "pages_for", "OutOfPages", "pool_write_ragged"]


@dataclasses.dataclass(frozen=True)
class LayerState:
    """What ONE layer keeps for ONE cached token — the model's declaration
    the cache manager allocates from (``model.cache_spec()`` returns one
    per layer). The engine knows no model: it reads ``kind`` to pick the
    attention that understands these rows, allocates one page pool per
    entry of ``rows`` and hands each layer its own pools back.

    kind   which attention reads the rows: ``"kv"`` (keys and values by
           head), ``"mla_latent"`` (one shared latent row a token),
           ``"kv_windowed"`` (keys and values with the heads side by side
           in one row, read by ``windowed_ragged_attention``) or
           ``"kda_state"`` (a delta-rule state a request, ``kda_ragged``)
    rows   name -> shape of a token's row, e.g. ``{"k": (KVH, Dh), "v":
           (KVH, Dh)}``, ``{"latent": (576,)}`` or ``{"k": (KVH * Dh,),
           "v": (KVH * Dh,)}``; of a ``per_request`` layer, of what it
           keeps a request: ``{"state": (H, Dk, Dv), "conv": (3, C)}``
    dtype  the pools' dtype (``row_dtypes`` names the rows that differ)
    query  shape of a token's query as the attention takes it (the
           start-up gate times the backends at this shape)
    row_align  the pools' minor dimension is rounded up to a multiple of
           this. A TPU stores a 576-wide row in 640 lanes whatever is
           declared; a kernel that copies whole pages by DMA needs the
           array to say so. The padding holds zeros and is never a value.
    window  tokens the layer may look back, the token itself included
           (token i sees token j where ``0 <= i - j < window``); ``None``
           = every earlier token. Layers of one ``(kind, window)`` form a
           **page group** (:class:`PageGroup`): a group with a window
           gives a page back once every token in it has slid out.
    per_request  the rows are kept once a REQUEST, whatever its length
           (a recurrent state): pools ``[max_slots + 1, *row]`` held by
           slot, in no page group. Such a state cannot be re-read by
           position, so the prefix cache, page sharing and migration
           refuse a model that has one.
    row_dtypes  name -> dtype of the rows whose pool is not ``dtype``'s
           (a float32 state beside a bfloat16 tail)
    """
    kind: str
    rows: dict
    dtype: object
    query: tuple
    row_align: int = 1
    window: int = None
    per_request: bool = False
    row_dtypes: dict = None

    @property
    def group(self):
        """The name of the layer's page group: its kind, and its window
        where it has one (``kv_windowed``, ``kv_windowed.w4096``); None
        for a layer that keeps a state a request and no pages."""
        if self.per_request:
            return None
        return self.kind if self.window is None \
            else f"{self.kind}.w{int(self.window)}"

    def row_dtype(self, name):
        return (self.row_dtypes or {}).get(name, self.dtype)

    def pool_row(self, name):
        """The row's shape as the pool holds it (minor dimension rounded
        up to ``row_align``)."""
        shape = tuple(int(d) for d in self.rows[name])
        a = int(self.row_align)
        return shape[:-1] + (-(-shape[-1] // a) * a,)

    def bytes_per_token(self, padded=False):
        """Bytes of one token's rows: as declared, or as the pools hold
        them. A state a request grows by nothing a token."""
        if self.per_request:
            return 0
        width = sum(int(np.prod(self.pool_row(n) if padded else shape))
                    for n, shape in self.rows.items())
        return width * jnp.dtype(self.dtype).itemsize

    def bytes_per_request(self):
        """Bytes of what a ``per_request`` layer keeps for one request."""
        if not self.per_request:
            return 0
        return sum(int(np.prod(self.pool_row(n)))
                   * jnp.dtype(self.row_dtype(n)).itemsize
                   for n in self.rows)


def kv_state(num_heads, num_kv_heads, head_dim, dtype):
    """The declaration of a layer with keys and values by head (MHA/GQA)."""
    row = (int(num_kv_heads), int(head_dim))
    return LayerState("kv", {"k": row, "v": row}, dtype,
                      (int(num_heads), int(head_dim)))


class OutOfPages(RuntimeError):
    """The pool cannot satisfy an allocation (caller may evict + retry)."""


def pages_for(n_tokens, page_size):
    """Pages needed to hold ``n_tokens`` (ceil division; 0 tokens -> 0)."""
    return -(-int(n_tokens) // int(page_size))


class BlockAllocator:
    """Refcounted free-list page allocator over ``num_pages`` physical
    pages.

    Page ids ``[0, reserved)`` are never handed out (page 0 is the scrap
    page). Purely host-side — allocation happens between decode steps on
    the scheduler thread, never inside the compiled step.

    **Refcounts + prefix sharing** (ISSUE 9): every live page carries a
    refcount (1 at :meth:`alloc`; :meth:`ref` adds readers — prefix-cache
    hits share one physical page across requests). :meth:`free` is a
    *deref*: the page returns to circulation only when the last reader
    drops it. A refcount-0 page whose content is still indexed by a
    :class:`~.prefix_cache.PrefixCache` (``self.cache``) parks in a
    **reclaimable LRU** instead of the free list — it stays a warm cache
    hit until the pool runs dry, at which point :meth:`alloc` reclaims
    LRU-oldest reclaimable pages (telling the cache to drop their index
    entries). A page with live readers is NEVER reclaimed — eviction
    pressure can only consume refcount-0 cached pages.
    """

    def __init__(self, num_pages, reserved=1):
        if num_pages <= reserved:
            raise ValueError(f"num_pages={num_pages} must exceed "
                             f"reserved={reserved}")
        self.num_pages = int(num_pages)
        self.reserved = int(reserved)
        # LIFO free list: recently-freed (still-warm) pages are reused first
        self._free = list(range(self.num_pages - 1, self.reserved - 1, -1))
        self._refs: dict[int, int] = {}      # page -> live reader count
        # refcount-0 pages still holding indexed prefix-cache content,
        # insertion order == LRU order (oldest first)
        self._reclaimable: dict[int, None] = {}
        self.cache = None                    # PrefixCache collaborator

    @property
    def capacity(self):
        """Allocatable pages (excludes the reserved scrap pages)."""
        return self.num_pages - self.reserved

    @property
    def free_pages(self):
        """Pages allocatable right now (truly free + reclaimable cached)."""
        return len(self._free) + len(self._reclaimable)

    @property
    def used_pages(self):
        """Pages held by live readers (cached-but-unreferenced excluded)."""
        return self.capacity - self.free_pages

    @property
    def cached_pages(self):
        """Refcount-0 pages parked for prefix-cache reuse."""
        return len(self._reclaimable)

    def refcount(self, page):
        return self._refs.get(int(page), 0)

    def shared_pages(self):
        """Pages with more than one live reader (prefix-shared)."""
        return sum(1 for rc in self._refs.values() if rc > 1)

    def occupancy_pct(self):
        return 100.0 * self.used_pages / self.capacity if self.capacity \
            else 0.0

    def can_alloc(self, n):
        return n <= self.free_pages

    def alloc(self, n):
        """-> list of ``n`` page ids, each with refcount 1; raises
        :class:`OutOfPages` when free + reclaimable pages are short
        (all-or-nothing: no partial grants). Reclaims LRU-oldest cached
        pages only after the free list is exhausted."""
        n = int(n)
        if n > self.free_pages:
            raise OutOfPages(
                f"need {n} page(s), {self.free_pages} free "
                f"of {self.capacity}")
        out = []
        for _ in range(n):
            if self._free:
                p = self._free.pop()
            else:
                p = next(iter(self._reclaimable))   # LRU oldest
                del self._reclaimable[p]
                if self.cache is not None:
                    self.cache.on_reclaim(p)
            self._refs[p] = 1
            out.append(p)
        return out

    def ref(self, pages):
        """Add one reader to each live page (prefix-cache sharing)."""
        for p in pages:
            p = int(p)
            rc = self._refs.get(p, 0)
            if rc <= 0:
                raise ValueError(
                    f"ref on page {p} with no live reader (free or "
                    "reclaimable pages must go through reuse_cached)")
            self._refs[p] = rc + 1

    def reuse_cached(self, page):
        """A prefix-cache hit on ``page``: add a reader, reactivating it
        from the reclaimable LRU if it was parked there. -> bool (False
        when the page is no longer available — stale index entry)."""
        page = int(page)
        if page in self._reclaimable:
            del self._reclaimable[page]
            self._refs[page] = 1
            return True
        rc = self._refs.get(page, 0)
        if rc > 0:
            self._refs[page] = rc + 1
            return True
        return False

    def free(self, pages):
        """Drop one reader per page. The last reader returns the page to
        the free list — or parks it in the reclaimable LRU when the
        prefix cache still indexes its content."""
        for p in pages:
            p = int(p)
            if p < self.reserved or p >= self.num_pages:
                raise ValueError(f"page {p} outside allocatable range")
            rc = self._refs.get(p, 0)
            if rc <= 0:
                raise ValueError(f"double free of page {p}")
            if rc > 1:
                self._refs[p] = rc - 1
                continue
            del self._refs[p]
            if self.cache is not None and self.cache.holds(p):
                self._reclaimable[p] = None     # newest = LRU tail
            else:
                self._free.append(p)


class PageGroup:
    """The layers of a model that keep the same thing for a token, as far
    back: one ``(kind, window)`` of their :class:`LayerState`. A group has
    its own :class:`BlockAllocator` over its own page count, and a request
    one block table in it (``GenerationRequest.group_pages``), which the
    round hands to the group's layers.

    A group with a ``window`` holds of a request only the pages some later
    token can still see: the scheduler frees, between rounds, every page
    that lies wholly before ``num_cached - window + 1`` (the first position
    the next round's first query token sees) and points its entry of the
    request's table at the scrap page.
    """

    def __init__(self, allocator, name="kv", window=None, layers=()):
        self.allocator = allocator
        self.name = name
        self.window = None if window is None else int(window)
        self.layers = list(layers)
        self.released = 0        # pages given back as they slid out
        self.peak_held = 0       # most pages with a live reader, and what
        self.peak_unreleased = 0  # their requests' tables spanned then

    @property
    def num_pages(self):
        return self.allocator.num_pages

    def first_live_page(self, num_cached, page_size):
        """The first page of a request's table that a token at position
        ``num_cached`` or later can still see."""
        if self.window is None:
            return 0
        return max(0, int(num_cached) - self.window + 1) // int(page_size)

    def admit_tokens(self, tokens, chunk):
        """Tokens of a ``tokens``-long prompt that have to fit in the
        group before its first round: all of them, or a window and the
        round's chunk."""
        if self.window is None or not chunk:
            return int(tokens)
        return min(int(tokens), self.window + int(chunk))

    def note(self, unreleased):
        """After a round: remember the fullest moment. ``unreleased`` is
        what the live requests' tables span, freed entries included."""
        held = self.allocator.used_pages
        if held >= self.peak_held:
            self.peak_held, self.peak_unreleased = held, int(unreleased)

    def stats(self):
        return {"pages": self.num_pages, "window": self.window,
                "layers": len(self.layers),
                "held": self.allocator.used_pages,
                "peak_held": self.peak_held,
                "peak_unreleased": self.peak_unreleased,
                "released": self.released}


class PagedKVCache:
    """Per-layer page pools + the allocators that parcel them out.

    ``pools[l]`` maps each row name of layer ``l``'s :class:`LayerState`
    to a jnp array ``[num_pages, page_size, *row_shape]`` — keys and
    values ``[.., KVH, Dh]`` for a GPT layer (GQA pools carry only the KV
    heads, an ``H/KVH`` memory cut), one ``[.., 576]`` latent pool for an
    MLA layer. Round writes happen *inside* the model's attention through
    ``pool_write_ragged`` below (a functional scatter); this class
    owns prefill writes, the allocators, and test/debug gathers.

    Layers are grouped by what they keep (:class:`PageGroup`, in order of
    first appearance): ``num_pages`` is one count for every group, or a
    dict by group name. A model whose layers all keep the same has one
    group, ``allocator`` and ``num_pages`` are that group's.

    A ``per_request`` layer (``state_layers``) is in no group: its pools
    are ``[max_slots + 1, *row]``, index 0 the scrap slot.
    """

    def __init__(self, specs, num_pages, page_size, reserved=1,
                 max_slots=None):
        self.specs = list(specs)
        self.num_layers = len(self.specs)
        self.page_size = int(page_size)
        self.state_layers = [l for l, spec in enumerate(self.specs)
                             if spec.per_request]
        self.state_slots = int(max_slots or 0)
        if self.state_layers and not max_slots:
            raise ValueError("layers that keep a state a request need "
                             "max_slots: their pools are held by slot")
        by_name = {}
        for l, spec in enumerate(self.specs):
            if not spec.per_request:
                by_name.setdefault(spec.group, []).append(l)
        if not by_name:
            raise ValueError("no layer of the model keeps rows a token: "
                             "the scheduler admits and evicts by pages")
        if isinstance(num_pages, dict):
            if set(num_pages) != set(by_name):
                raise ValueError(
                    f"num_pages names the page groups {sorted(num_pages)}; "
                    f"the model's layers form {sorted(by_name)}")
            counts = num_pages
        else:
            counts = dict.fromkeys(by_name, num_pages)
        self.groups = [
            PageGroup(BlockAllocator(int(counts[name]), reserved=reserved),
                      name, self.specs[layers[0]].window, layers)
            for name, layers in by_name.items()]
        # a layer's page group; None for a layer that keeps a state
        self.group_of = [None if spec.per_request else 0
                         for spec in self.specs]
        for g, group in enumerate(self.groups):
            for l in group.layers:
                self.group_of[l] = g
        self.pools = [
            {name: jnp.zeros(
                ((self.state_slots + 1,) if spec.per_request else
                 (self.groups[self.group_of[l]].num_pages, self.page_size))
                + spec.pool_row(name), spec.row_dtype(name))
             for name in spec.rows}
            for l, spec in enumerate(self.specs)]

    @property
    def allocator(self):
        """The first group's allocator: THE allocator of a model with one
        page group."""
        return self.groups[0].allocator

    @property
    def num_pages(self):
        return self.groups[0].num_pages

    def require_one_unwindowed_group(self, what):
        """``what`` (the prefix cache, page sharing, migration) identifies
        a request's cached state with ONE list of pages that all hold
        their tokens for good; refuse a model that keeps it otherwise."""
        if self.state_layers:
            raise ValueError(
                f"{what} cannot serve a model whose layers "
                f"{self.state_layers} keep a recurrent state a request: a "
                "state cannot be re-read by position, so it cannot be a "
                "hit, be shared or be moved (snapshots of a state: "
                "ROADMAP B5)")
        windowed = [g.name for g in self.groups if g.window is not None]
        if windowed:
            raise ValueError(
                f"{what} cannot serve a model with a windowed page group "
                f"({windowed}): a page that slid out of a window was freed "
                "and cannot be a hit, be shared or be moved")
        if len(self.groups) > 1:
            raise ValueError(
                f"{what} handles one page group a request; the model's "
                f"layers form {[g.name for g in self.groups]}")

    @property
    def dtype(self):
        return self.specs[0].dtype

    def state_bytes_per_slot(self):
        """Bytes the ``per_request`` layers keep for one request."""
        return sum(self.specs[l].bytes_per_request()
                   for l in self.state_layers)

    @property
    def k(self):
        """The key pools by layer (layers of kind ``kv``; read-only view)."""
        return [p["k"] for p in self.pools]

    @property
    def v(self):
        return [p["v"] for p in self.pools]

    def bytes_per_token(self, padded=False):
        """Bytes one cached token takes in each layer, by layer: as the
        model declares its rows, or as the pools hold them."""
        return [spec.bytes_per_token(padded) for spec in self.specs]

    def nbytes(self):
        return sum(a.size * a.dtype.itemsize
                   for p in self.pools for a in p.values())

    def occupancy_pct(self):
        """Of the fullest group."""
        return max(g.allocator.occupancy_pct() for g in self.groups)

    def write_rows(self, layer, rows, pages, length):
        """Write one request's prefill rows (name -> ``[S, *row_shape]``
        with ``S >= length``; rows past ``length`` are padding and
        dropped) into its ``pages``. The tail of the last page stays
        whatever it was — reads are masked by ``context_lens``."""
        n = len(pages)
        cap = n * self.page_size
        if length > cap:
            raise ValueError(f"{length} tokens > {n} page capacity {cap}")
        idx = jnp.asarray(np.asarray(pages, np.int32))
        pools = self.pools[layer]
        for name, new in rows.items():
            pool = pools[name]
            arr = _fit(jnp.asarray(new)[:length].astype(pool.dtype), pool)
            pad = cap - length
            if pad:
                arr = jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))
            arr = arr.reshape((n, self.page_size) + pool.shape[2:])
            pools[name] = pool.at[idx].set(arr)

    def write_prefill(self, layer, k_new, v_new, pages, length):
        """:meth:`write_rows` for a layer of keys and values."""
        self.write_rows(layer, {"k": k_new, "v": v_new}, pages, length)

    def gather(self, layer, pages, length, which="k"):
        """Debug/test readback: the first ``length`` tokens of a request's
        pages as one dense ``[length, *row_shape]`` array."""
        pool = self.pools[layer][which]
        idx = jnp.asarray(np.asarray(pages, np.int32))
        dense = pool[idx].reshape((-1,) + pool.shape[2:])
        return dense[:length, ..., :self.specs[layer].rows[which][-1]]


# ------------------------------------------------------------ pool writers
# The scatter a model's attention calls on its own pools inside the
# round's program. Generic in the row's shape: a pool is
# [P, page, *row_shape] and ``new`` carries the same trailing dims (a
# minor dimension narrower than the pool's is padded with zeros).

def _fit(new, pool):
    short = pool.shape[-1] - new.shape[-1]
    if short == 0:
        return new
    return jnp.pad(new, ((0, 0),) * (new.ndim - 1) + ((0, short),))


def pool_write_ragged(pool, new, block_tables, row_starts, row_lens,
                      kv_lens):
    """Ragged serving round: scatter the FLAT token stream's rows
    (`new` [1, T, *row]) into the page pool — flat token t belongs to
    row ``row_ids[t]`` at absolute position ``positions[t]`` (segment
    decomposition via ``ragged_row_index``, one copy with the attention
    reference); pad tokens are redirected to the reserved scrap page 0
    (never read), so one launch serves any prefill/decode mix."""
    def fwd(p, n, bt, rs, rl, kl):
        from ..ops.pallas.ragged_attention import ragged_row_index
        T = n.shape[1]
        page = p.shape[1]
        rid, pos, valid = ragged_row_index(rs, rl, kl, T)
        logical = jnp.clip(pos // page, 0, bt.shape[1] - 1)
        phys = bt.astype(jnp.int32)[rid, logical]
        phys = jnp.where(valid, phys, 0)                  # scrap redirect
        slot = jnp.where(valid, pos % page, 0)
        return p.at[phys, slot].set(_fit(n[0].astype(p.dtype), p))
    return apply("ragged_kv_write", fwd,
                 [pool, new, block_tables, row_starts, row_lens, kv_lens])
