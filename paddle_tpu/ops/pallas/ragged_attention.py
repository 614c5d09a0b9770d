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
* :func:`ragged_paged_attention` — the Pallas kernel: grid
  ``(T, max_pages)``, with the per-token row id and context length in
  **scalar prefetch**, so each grid step's BlockSpec index_map resolves
  ``block_tables[row_ids[t], i]`` and the DMA streams exactly the pages
  the token's row owns. The flat-token grid is what makes the launch
  ragged-native: a token costs its own pages, never a bucket's padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (_grouped, _kernel,
                              paged_attention_reference)

LANES = 128

__all__ = ["ragged_row_index", "ragged_paged_attention_reference",
           "ragged_paged_attention"]


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


def ragged_paged_attention(q, k_cache, v_cache, row_starts, row_lens,
                           kv_lens, block_tables, scale=None,
                           interpret=False):
    """Pallas kernel: grid ``(T, max_pages)`` over the FLAT token stream.
    Per-token row ids and context lengths ride scalar prefetch next to
    the block tables, so the k/v BlockSpec index_maps resolve
    ``block_tables[row_ids[t], i]`` and the DMA streams each token's own
    row's pages — one launch for any prefill/decode mix, no bucket
    shapes. The online-softmax body is the decode kernel's (a ragged
    token IS a decode row with its own causal context length)."""
    T, H, D = q.shape
    KVH = k_cache.shape[2]
    groups = _grouped(H, KVH)
    num_pages, page_size = k_cache.shape[0], k_cache.shape[1]
    max_pages = block_tables.shape[1]
    scale = np.float32(scale if scale is not None else 1.0 / np.sqrt(D))
    rid, pos, valid = ragged_row_index(row_starts, row_lens, kv_lens, T)
    ctx = jnp.where(valid, pos + 1, 0).astype(jnp.int32)

    def _page(t, i, rid_s, ctx_s, blk):
        # clamp: pad rows carry scrap/garbage table entries; the kernel's
        # in-context mask already zeroes such pages' contribution
        return (jnp.clip(blk[rid_s[t], i], 0, num_pages - 1), 0, 0, 0)

    def _ragged_body(rid_ref, ctx_ref, blk_ref, q_ref, k_ref, v_ref,
                     o_ref, m_scr, l_scr, acc_scr):
        # the decode body verbatim: program_id(0) is the flat token, its
        # context length rides ctx_ref where decode's len_ref sat
        _kernel(blk_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                l_scr, acc_scr, scale=scale, page_size=page_size,
                groups=groups)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # row_ids, ctx_lens, block_tables
        grid=(T, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda t, i, r, c, b: (t, 0, 0)),
            pl.BlockSpec((1, page_size, KVH, D), _page),
            pl.BlockSpec((1, page_size, KVH, D), _page),
        ],
        out_specs=pl.BlockSpec((1, H, D),
                               lambda t, i, r, c, b: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, LANES), jnp.float32),
            pltpu.VMEM((H, LANES), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            _ragged_body,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, H, D), q.dtype),
            interpret=interpret,
            # the HLO instruction's name, hence the device trace's: the
            # benchmark's per-kernel metrics find the kernel by this prefix
            name="ragged_paged_attention",
        )(rid, ctx, block_tables.astype(jnp.int32), q, k_cache, v_cache)
