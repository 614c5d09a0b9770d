"""Ragged paged attention over keys and values by head, grouped-query, with
an optional WINDOW — mixed prefill+decode rows in one launch, reading only
the pages a layer can see.

What is cached for a token is its keys and values of every KV head side by
side, one row of ``kv_heads * head_dim`` values each (a page of one pool is
one contiguous block and a head's part of it a lane-aligned slice):

    q            [T, H, D]        flat query tokens; head h reads KV head
                 ``h // (H // kv_heads)``
    k_pool, v_pool   [num_pages, page_size, kv_heads * D]
    row_starts / row_lens / kv_lens / block_tables   as
                 ``ragged_attention.py`` takes them
    window       tokens a query may look back, itself included: token i
                 sees token j where ``0 <= i - j < window``; ``None`` =
                 every ``j <= i``
    ->           [T, H, D]

A block table of a windowed layer may point the entries of pages that lie
wholly before a row's window at any page (the cache manager frees those
pages and points them at the scrap page): neither backend's result depends
on what such a page holds, and the kernel never fetches it.

Two backends, the contract of ``mla_ragged_attention.py``:

* :func:`windowed_ragged_attention_reference` — the XLA twin: gather every
  token's row pages, two einsums in float32 (CPU, tests, the start-up
  gate's other leg). Its temporaries are ``T x table width x kv_heads x
  D``: decode shapes and short tables only at real sizes.
* :func:`windowed_ragged_attention` — the Pallas kernel. The flat stream
  is cut into **work items** of at most ``block_q`` query tokens of one row
  (``mla_ragged_attention.work_items``), so an item's ``block_q * H /
  kv_heads`` query rows of one KV head share every page they read. Grid
  ``(items,)``; inside, a loop over exactly the pages the item can see
  (:func:`item_pages`: from the page of the first position its first token
  sees to the page of its last token), each page of keys and of values
  fetched whole by a double-buffered DMA addressed through the block table
  in scalar memory, and every KV head attended from its slice of the page.
  A step that has nothing to read is never launched: a window layer at a
  16k context runs 17 page steps, not 64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mla_ragged_attention import work_items
from .ragged_attention import ragged_row_index

LANES = 128
NEG = -1e30

__all__ = ["windowed_ragged_attention",
           "windowed_ragged_attention_reference", "item_pages"]


def item_pages(pos0, nq, page, window):
    """The pages ``[first, end)`` a work item reads: its ``nq`` tokens
    start at absolute position ``pos0``; the first sees back to
    ``pos0 - window + 1`` (or to 0 without a window), the last sits at
    ``pos0 + nq - 1``. An unused item (``nq == 0``) reads none. Scalar
    integer arithmetic: the kernel runs it on scalars in SMEM, a test on
    plain numbers."""
    first = 0 if window is None \
        else jnp.maximum(pos0 - (window - 1), 0) // page
    end = jnp.where(nq > 0, (pos0 + nq - 1) // page + 1, first)
    return first, end


def windowed_ragged_attention_reference(q, k_pool, v_pool, row_starts,
                                        row_lens, kv_lens, block_tables,
                                        scale=None, window=None):
    """The XLA twin: per-token gather of the row's pages, float32 math.
    Pad tokens come back zeroed. -> ``[T, H, D]``."""
    T, H, D = q.shape
    page = k_pool.shape[1]
    kvh = k_pool.shape[2] // D
    scale = np.float32(scale if scale is not None else 1.0 / np.sqrt(D))
    rid, pos, valid = ragged_row_index(row_starts, row_lens, kv_lens, T)
    vbt = jnp.take(block_tables.astype(jnp.int32), rid, axis=0)   # [T, mp]
    S = vbt.shape[1] * page
    k = k_pool[vbt].reshape(T, S, kvh, D)
    v = v_pool[vbt].reshape(T, S, kvh, D)
    qg = q.reshape(T, kvh, H // kvh, D)
    s = jnp.einsum("tkgd,tskd->tkgs", qg, k, precision="highest",
                   preferred_element_type=jnp.float32) * scale
    at = jnp.arange(S, dtype=jnp.int32)[None, :]
    seen = (at <= pos[:, None]) & valid[:, None]
    if window is not None:
        seen &= pos[:, None] - at < window
    # a page outside the window may hold anything, a value that is no
    # number included: what is not seen takes no part in either product
    s = jnp.where(seen[:, None, None, :], s, NEG)
    p = jnp.where(seen[:, None, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    v = jnp.where(seen[:, :, None, None], v, 0)
    out = jnp.einsum("tkgs,tskd->tkgd", p, v, precision="highest",
                     preferred_element_type=jnp.float32)
    return jnp.where(valid[:, None, None], out.reshape(T, H, D),
                     0.0).astype(q.dtype)


def _kernel(row_ref, pos0_ref, nq_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
            kbuf, vbuf, sem, m_scr, l_scr, acc_scr, *, scale, page, group,
            kv_heads, head_dim, block_q, window, decode_rows):
    w = pl.program_id(0)
    row, pos0, nq = row_ref[w], pos0_ref[w], nq_ref[w]
    first, end = item_pages(pos0, nq, page, window)
    D = head_dim

    def copies(i, slot):
        at = bt_ref[row, i]
        return (pltpu.make_async_copy(k_ref.at[at], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_ref.at[at], vbuf.at[slot],
                                      sem.at[1, slot]))

    def attend(m_rows):
        """The item's first ``m_rows`` query rows of every KV head
        (``decode_rows`` for a decode item, all of the block for a
        chunk's) against its pages."""
        m_scr[:, :m_rows] = jnp.full((kv_heads, m_rows, LANES), NEG,
                                     jnp.float32)
        l_scr[:, :m_rows] = jnp.zeros((kv_heads, m_rows, LANES),
                                      jnp.float32)
        acc_scr[:, :m_rows] = jnp.zeros((kv_heads, m_rows, D), jnp.float32)
        for c in copies(first, 0):
            c.start()
        # query row m of a KV head is token m // group of the item
        q_pos = pos0 + jax.lax.broadcasted_iota(
            jnp.int32, (m_rows, page), 0) // group
        k_off = jax.lax.broadcasted_iota(jnp.int32, (m_rows, page), 1)

        def body(i, carry):
            slot = (i - first) % 2

            @pl.when(i + 1 < end)
            def _():
                for c in copies(i + 1, 1 - slot):
                    c.start()

            for c in copies(i, slot):
                c.wait()
            k_pos = i * page + k_off
            seen = (k_pos <= q_pos) & (q_pos < pos0 + nq)
            if window is not None:
                seen &= q_pos - k_pos < window
            # a slot of an edge page that no query sees may hold anything
            # (the tail of a row's last page, a page handed on by another
            # request): it takes no part in the second product either
            live = (jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
                    + i * page) < pos0 + nq
            for h in range(kv_heads):
                cols = slice(h * D, (h + 1) * D)
                keys = kbuf[slot, :, cols]                    # [page, D]
                vals = jnp.where(live, vbuf[slot, :, cols], 0)
                s = jax.lax.dot_general(
                    q_ref[0, h, :m_rows], keys, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(seen, s, NEG)
                m_prev = m_scr[h, :m_rows]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.where(seen, jnp.exp(s - m_new[:, :1]), 0.0)
                l_scr[h, :m_rows] = alpha * l_scr[h, :m_rows] \
                    + jnp.sum(p, axis=1, keepdims=True)
                acc_scr[h, :m_rows] = acc_scr[h, :m_rows] * alpha[:, :1] \
                    + jax.lax.dot_general(
                        p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_scr[h, :m_rows] = m_new
            return carry

        jax.lax.fori_loop(first, end, body, 0)
        l = l_scr[:, :m_rows][..., :1]
        o_ref[0, :, :m_rows] = (acc_scr[:, :m_rows]
                                / jnp.where(l > 0.0, l, 1.0)
                                ).astype(o_ref.dtype)

    # a decode row inside a mixed round is one token: its item does its
    # share of the block's work, not all of it
    @pl.when((nq > 0) & (nq * group <= decode_rows))
    def _():
        attend(decode_rows)

    if decode_rows < block_q * group:
        @pl.when(nq * group > decode_rows)
        def _():
            attend(block_q * group)


@functools.partial(jax.jit, static_argnames=("scale", "window", "block_q",
                                             "interpret"))
def _call(q, k_pool, v_pool, row_starts, row_lens, kv_lens, block_tables,
          *, scale, window, block_q, interpret):
    T, H, D = q.shape
    page, width = k_pool.shape[1:]
    kvh = width // D
    G = H // kvh
    M = block_q * G
    row, pos0, nq, tok = work_items(row_starts, row_lens, kv_lens, T,
                                    block_q)
    n_items = row.shape[0]
    # [items, block_q, kvh, G, D] -> a KV head's query rows side by side
    q_ext = jnp.concatenate([q, jnp.zeros((1, H, D), q.dtype)])
    q_items = q_ext[tok].reshape(n_items, block_q, kvh, G, D) \
        .transpose(0, 2, 1, 3, 4).reshape(n_items, kvh, M, D)
    # the rows a one-token item attends with: its G, rounded up to whole
    # sublane tiles of either dtype (the rest belong to tokens the item
    # does not have and are masked)
    decode_rows = min(M, -(-G // 16) * 16)
    kernel = functools.partial(
        _kernel, scale=np.float32(scale), page=page, group=G, kv_heads=kvh,
        head_dim=D, block_q=block_q, window=window, decode_rows=decode_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,       # item row, first position, tokens, bt
        grid=(n_items,),
        in_specs=[
            pl.BlockSpec((1, kvh, M, D), lambda w, *_: (w, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kvh, M, D), lambda w, *_: (w, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page, width), k_pool.dtype),
            pltpu.VMEM((2, page, width), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((kvh, M, LANES), jnp.float32),
            pltpu.VMEM((kvh, M, LANES), jnp.float32),
            pltpu.VMEM((kvh, M, D), jnp.float32),
        ],
    )
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_items, kvh, M, D), q.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 2 ** 20),
            # the HLO instruction's name, hence the device trace's: the
            # benchmark's per-kernel metrics find the kernel by this prefix
            name="windowed_ragged_attention",
        )(row, pos0, nq, block_tables.astype(jnp.int32), q_items, k_pool,
          v_pool)
    # back to the flat stream; unused slots of an item land on a spare row
    out = out.reshape(n_items, kvh, block_q, G, D).transpose(0, 2, 1, 3, 4)
    flat = jnp.zeros((T + 1, H, D), q.dtype)
    flat = flat.at[tok.reshape(-1)].set(out.reshape(-1, H, D))
    return flat[:T]


def windowed_ragged_attention(q, k_pool, v_pool, row_starts, row_lens,
                              kv_lens, block_tables, scale=None,
                              window=None, block_q=None, interpret=False):
    """The Pallas kernel (module docstring). ``block_q`` defaults to 8
    where the launch cannot hold a multi-token row worth blocking (no more
    tokens than rows: a decode round), else 32. Jitted inside, so a
    model's layers of one window share one traced kernel and a round's
    program lowers it once a window. -> ``[T, H, D]``, pad tokens
    zeroed."""
    T, H, D = q.shape
    if block_q is None:
        block_q = 8 if T <= row_starts.shape[0] else 32
    return _call(q, k_pool, v_pool, row_starts, row_lens, kv_lens,
                 block_tables,
                 scale=float(scale if scale is not None
                             else 1.0 / np.sqrt(D)),
                 window=None if window is None else int(window),
                 block_q=int(block_q), interpret=bool(interpret))
