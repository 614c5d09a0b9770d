"""Ragged paged attention — mixed prefill+decode rows in ONE launch.

Reference capability: Ragged Paged Attention (arxiv 2604.15464) — the
kernel that lets a serving scheduler stop shaping rounds around the
compile cache. Input is a **flattened token stream**: every row of the
continuous batch (a single-token decode step, a chunked-prefill segment,
a prompt tail behind a prefix-cache hit) contributes its tokens to one
``[total_tokens, H, D]`` query array, described by per-row metadata:

    q            [T, H, D]        flat query tokens, rows back to back
    k/v_cache    [num_pages, page_size, KVH, D]  (GQA pools, KVH <= H)
    row_starts   [R] int32        first flat index of each row's tokens
                                  (nondecreasing; unused rows carry T)
    row_lens     [R] int32        query tokens this launch (0 = unused row)
    kv_lens      [R] int32        TOTAL KV tokens per row AFTER this
                                  launch's writes (prefix + this segment)
    block_tables [R, max_pages]   physical page ids per row

Query token ``i`` of row ``r`` sits at absolute position
``kv_lens[r] - row_lens[r] + i`` and attends causally over its row's
pages: every KV position ``<= `` its own (write-then-attend, the same
order as the decode step — the segment's K/V is already in the pool).
A decode row is simply ``row_lens == 1``; a whole-prompt prefill is
``row_lens == kv_lens``. One launch covers any mix — no (batch, seq)
bucket matrix, no per-shape programs beyond the padded ``T`` itself.

Two backends, same contract as ``paged_attention.py``:

* :func:`ragged_paged_attention_reference` — the jnp gather/segment
  formulation (CPU-parity source of truth): per-token row ids come from
  ``searchsorted`` over ``row_starts`` (the segment decomposition), and
  the causal mask is per-token ``position + 1`` context lengths over the
  row's gathered pages.
* :func:`ragged_paged_attention` — the Pallas kernel. The flat stream is
  cut into **work items** of at most ``block_q`` query tokens of one row
  (``mla_ragged_attention.work_items``; a decode row is one item of one
  token). Grid ``(items,)``, the item plan and the block table in scalar
  prefetch; inside, a loop over exactly the pages the item's last token
  can see, ``pages`` of them a step, each page of keys and of values
  fetched by a double-buffered DMA addressed through the block table (an
  item's last step fetches the next item's first block). An unused item
  reads nothing, a decode row reads its own pages once, a chunk row its
  pages once an item. :func:`item_schedule` picks ``block_q`` and
  ``pages`` from the shapes alone.

  Every head of an item is attended in one product a step. A decode item
  takes its block of keys as they lie, ``[keys x KVH, D]``, against its
  ``H`` query rows and keeps only each head's own columns (the matrix
  unit's time for a handful of rows is its weight loads, the same for
  one head or all). An item of several tokens takes the block head-major,
  ``[KVH, keys, D]``, against ``[KVH, G x tokens, D]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _grouped, paged_attention_reference

LANES = 128
NEG = -1e30
# keys of a 128-wide head a loop step reads (item_schedule)
BLOCK_KEYS = 128
# launches of up to this many tokens are decode rounds (item_schedule)
DECODE_PAD = 32

__all__ = ["ragged_row_index", "ragged_paged_attention_reference",
           "ragged_paged_attention", "item_schedule", "item_plan"]


def ragged_row_index(row_starts, row_lens, kv_lens, total_tokens):
    """Per-token segment decomposition of the flat stream — the one copy
    of the ragged index math, shared by the reference, the kernel wrapper
    and the model's pool scatter. For each flat token ``t``:

    * ``row_ids[t]`` — the row owning token ``t`` (``searchsorted`` over
      the nondecreasing ``row_starts``; padding tokens past the last used
      row resolve to it and are masked by ``valid``)
    * ``positions[t]`` — the token's absolute position in its row's KV
      stream (``kv_lens[r] - row_lens[r] + offset``)
    * ``valid[t]`` — False for pad tokens (offset beyond the row's len);
      their writes go to the scrap page and their outputs are garbage the
      caller discards.

    All jnp — safe under jit (``total_tokens`` must be static)."""
    t = jnp.arange(total_tokens, dtype=jnp.int32)
    rs = row_starts.astype(jnp.int32)
    rid = jnp.clip(
        jnp.searchsorted(rs, t, side="right").astype(jnp.int32) - 1,
        0, rs.shape[0] - 1)
    off = t - rs[rid]
    rl = row_lens.astype(jnp.int32)[rid]
    valid = (off >= 0) & (off < rl)
    pos = kv_lens.astype(jnp.int32)[rid] - rl + off
    pos = jnp.where(valid, pos, 0)
    return rid, pos, valid


def ragged_paged_attention_reference(q, k_cache, v_cache, row_starts,
                                     row_lens, kv_lens, block_tables,
                                     scale=None):
    """jnp gather/segment formulation (always-correct path; the serving
    engine's single ragged program compiles this on any device).

    The segment decomposition turns the ragged batch into per-token
    virtual decode rows: token ``t`` attends its row's pages with context
    length ``positions[t] + 1`` — exactly the causal prefix including the
    token's own just-written KV — so the grouped-GQA gather math is ONE
    copy shared with :func:`~.paged_attention.paged_attention_reference`.
    Pad tokens get context 0 (output zeroed). Returns ``[T, H, D]``."""
    T = q.shape[0]
    rid, pos, valid = ragged_row_index(row_starts, row_lens, kv_lens, T)
    vbt = jnp.take(block_tables.astype(jnp.int32), rid, axis=0)  # [T, mp]
    ctx = jnp.where(valid, pos + 1, 0).astype(jnp.int32)
    return paged_attention_reference(q, k_cache, v_cache, vbt, ctx,
                                     scale=scale)


def item_schedule(page_size, head_dim, tokens):
    """The kernel's blocks, from its shapes alone: -> ``(block_q, pages)``,
    the most query tokens of one row a work item holds and the pages its
    loop fetches a step.

    A step takes some ``BLOCK_KEYS`` keys of ``head_dim`` (eight of GPT's
    16-token pages, one page of 256): on a v5e 4, 8 and 16 pages of 16 a
    step read a decode round alike, and 4 a prompt tail's round slower
    (PERF.md, section 6). A launch of no more tokens than ``DECODE_PAD``
    holds decode rows and short prompts: small items, whose query and
    output blocks cost little; a larger launch carries prompt chunks,
    which read their pages once an item."""
    keys = max(BLOCK_KEYS * LANES // head_dim, page_size)
    pages = max(1, keys // page_size)
    block_q = min(tokens, 8 if tokens <= DECODE_PAD else 32)
    return block_q, pages


def item_plan(row_lens, kv_lens, page_size, block_q, pages):
    """What a launch of these rows does, counted on the host (numpy):
    -> ``(items, blocks)``, the work items that read at least one page and
    the page blocks they fetch. A row of ``n`` tokens ending at context
    ``kv`` is ``ceil(n / block_q)`` items; item ``j`` reads the pages up
    to its last token's, ``(kv - n + min(n, (j + 1) * block_q) - 1) //
    page_size + 1``, ``pages`` a step."""
    items = blocks = 0
    for n, kv in zip(np.asarray(row_lens).tolist(),
                     np.asarray(kv_lens).tolist()):
        for j in range(-(-int(n) // block_q)):
            last = kv - n + min(n, (j + 1) * block_q) - 1
            items += 1
            blocks += -(-(last // page_size + 1) // pages)
    return items, blocks


def _kernel(row_ref, pos0_ref, nq_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, slot_ref, m_scr, l_scr, acc_scr, *, scale, pages,
            group):
    w = pl.program_id(0)
    row, pos0, nq = row_ref[w], pos0_ref[w], nq_ref[w]
    num_pages, page, KVH, D = k_hbm.shape
    G, N = group, pages * page
    H = KVH * G

    def pages_of(w):
        """The pages item ``w``'s last token can see; an unused item's
        none."""
        nq = nq_ref[w]
        return jnp.where(nq > 0, (pos0_ref[w] + nq - 1) // page + 1, 0)

    n_pages = pages_of(w)
    n_blocks = (n_pages + pages - 1) // pages
    # the next item, whose first block this one's last step fetches (the
    # used items come first, so an item after the first has it fetched)
    after = jnp.minimum(w + 1, pl.num_programs(0) - 1)
    ahead = (w + 1 < pl.num_programs(0)) & (nq_ref[after] > 0)

    def fetch(row, n_pages, b, slot, wait):
        """Start (or wait for) the DMAs of block ``b``'s pages of keys and
        values into ``slot``: one a page, the last block's cut short."""
        def one(j, carry):
            # clamp: a table entry is the engine's to fill; a wrong one
            # must not drive a DMA out of the pool
            at = jnp.clip(bt_ref[row, b * pages + j], 0, num_pages - 1)
            for i, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                copy = pltpu.make_async_copy(pool.at[at], buf.at[slot, j],
                                             sem.at[i, slot])
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(pages, n_pages - b * pages), one, 0)

    def attend(stats, step):
        """The online softmax over the item's blocks: ``stats`` are the
        running max, sum and output of its query rows (views of the
        scratch), ``step(b, keys, values)`` -> ``(scores, seen, product)``
        where ``product(p)`` is ``p`` against the values."""
        m_ref, l_ref, acc_ref = stats
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        first = jnp.where(w == 0, 0, slot_ref[0])

        @pl.when(w == 0)
        def _():
            fetch(row, n_pages, 0, first, False)

        def body(b, carry):
            slot = (first + b) % 2

            @pl.when(b + 1 < n_blocks)
            def _():
                fetch(row, n_pages, b + 1, 1 - slot, False)

            @pl.when((b + 1 == n_blocks) & ahead)
            def _():
                fetch(row_ref[after], pages_of(after), 0, 1 - slot, False)

            fetch(row, n_pages, b, slot, True)
            # a slot past the item's last token may hold anything (the
            # tail of its last page, a page of this block not fetched):
            # it takes no part in the second product either
            live = (jax.lax.broadcasted_iota(jnp.int32, (N, 1, 1), 0)
                    + b * N) < pos0 + nq
            s, seen, product = step(
                b, kbuf[slot].reshape(N, KVH, D),
                jnp.where(live, vbuf[slot].reshape(N, KVH, D), 0))
            s = jnp.where(seen, s * scale, NEG)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(seen, jnp.exp(s - m_new[..., :1]), 0.0)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha[..., :1] + product(p)
            m_ref[...] = m_new
            return carry

        jax.lax.fori_loop(0, n_blocks, body, 0)
        slot_ref[0] = (first + n_blocks) % 2
        l = l_ref[...][..., :1]
        return acc_ref[...] / jnp.where(l > 0.0, l, 1.0)

    @pl.when(nq == 1)
    def _():
        # a decode item: its H query rows against the block as it lies,
        # [keys x KVH, D]; query row h keeps the columns of its KV head
        q = q_ref[0, 0]                                       # [H, D]
        col = jax.lax.broadcasted_iota(jnp.int32, (H, N * KVH), 1)
        own = col % KVH == jax.lax.broadcasted_iota(
            jnp.int32, (H, N * KVH), 0) // G

        def step(b, k, v):
            k, v = k.reshape(N * KVH, D), v.reshape(N * KVH, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            return s, own & (col // KVH + b * N <= pos0), \
                lambda p: jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        out = attend((m_scr.at[0, :H], l_scr.at[0, :H], acc_scr.at[0, :H]),
                     step)
        o_ref[0, 0] = out.astype(o_ref.dtype)

    block_q = q_ref.shape[1]
    if block_q > 1:
        @pl.when(nq > 1)
        def _():
            # an item of several tokens: [t, H, D] -> [KVH, G x t, D], query
            # row m of a KV head is its group member m // t, token m % t;
            # the block goes head-major, turned in float32 (a 16-bit
            # array's rows are packed in pairs)
            M = G * block_q
            q = jnp.swapaxes(q_ref[0], 0, 1).reshape(KVH, M, D)
            tok = jax.lax.broadcasted_iota(jnp.int32, (M, N), 0) % block_q
            k_off = jax.lax.broadcasted_iota(jnp.int32, (M, N), 1)

            def step(b, k, v):
                k, v = (jnp.swapaxes(x.astype(jnp.float32), 0, 1)
                        .astype(x.dtype) for x in (k, v))
                s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                        preferred_element_type=jnp.float32)
                return s, (k_off + b * N <= pos0 + tok) & (tok < nq), \
                    lambda p: jax.lax.dot_general(
                        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)

            out = attend((m_scr.at[:, :M], l_scr.at[:, :M],
                          acc_scr.at[:, :M]), step)
            o_ref[0] = jnp.swapaxes(out.reshape(H, block_q, D), 0, 1) \
                .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _call(q, k_cache, v_cache, row_starts, row_lens, kv_lens, block_tables,
          *, scale, interpret):
    # the latent kernel's module imports ragged_row_index from this one
    from .mla_ragged_attention import work_items
    T, H, D = q.shape
    page, KVH = k_cache.shape[1], k_cache.shape[2]
    G = _grouped(H, KVH)
    block_q, pages = item_schedule(page, D, T)
    row, pos0, nq, tok = work_items(row_starts, row_lens, kv_lens, T,
                                    block_q)
    n_items = row.shape[0]
    q_ext = jnp.concatenate([q, jnp.zeros((1, H, D), q.dtype)])
    item = pl.BlockSpec((1, block_q, H, D), lambda w, *_: (w, 0, 0, 0))
    M = max(G * block_q, H)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,       # item row, first position, tokens, bt
        grid=(n_items,),
        in_specs=[item, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=item,
        scratch_shapes=[
            pltpu.VMEM((2, pages, page, KVH, D), k_cache.dtype),
            pltpu.VMEM((2, pages, page, KVH, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),      # the next item's first slot
            pltpu.VMEM((KVH, M, LANES), jnp.float32),
            pltpu.VMEM((KVH, M, LANES), jnp.float32),
            pltpu.VMEM((KVH, M, D), jnp.float32),
        ],
    )
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_kernel, scale=np.float32(scale), pages=pages,
                              group=G),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_items, block_q, H, D),
                                           q.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 2 ** 20),
            # the HLO instruction's name, hence the device trace's: the
            # benchmark's per-kernel metrics find the kernel by this prefix
            name="ragged_paged_attention",
        )(row, pos0, nq, block_tables.astype(jnp.int32), q_ext[tok],
          k_cache, v_cache)
    # back to the flat stream, a gather: token t of row r is slot
    # (t - start) % block_q of the row's item (t - start) // block_q
    rid, _, valid = ragged_row_index(row_starts, row_lens, kv_lens, T)
    per_row = (row_lens.astype(jnp.int32) + block_q - 1) // block_q
    off = jnp.arange(T, dtype=jnp.int32) - row_starts.astype(jnp.int32)[rid]
    at = ((jnp.cumsum(per_row) - per_row)[rid] + off // block_q) * block_q \
        + off % block_q
    flat = out.reshape(n_items * block_q, H, D)[jnp.where(valid, at, 0)]
    return jnp.where(valid[:, None, None], flat, 0).astype(q.dtype)


def ragged_paged_attention(q, k_cache, v_cache, row_starts, row_lens,
                           kv_lens, block_tables, scale=None,
                           interpret=False):
    """The Pallas kernel (module docstring): one launch for any
    prefill/decode mix. Jitted inside, so a model's layers share one
    traced kernel and a round's program lowers it once a token pad.
    -> ``[T, H, D]``, pad tokens zeroed."""
    D = q.shape[-1]
    return _call(q, k_cache, v_cache, row_starts, row_lens, kv_lens,
                 block_tables,
                 scale=float(scale if scale is not None
                             else 1.0 / np.sqrt(D)),
                 interpret=bool(interpret))
