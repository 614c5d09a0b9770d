"""Paged attention (decode) — Pallas TPU kernel + jnp reference path.

Reference capability: paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu (paged KV cache for serving: per-
sequence page tables into a shared block pool, one query token per step).
TPU-native design: the page table rides Pallas scalar prefetch, so each
grid step's BlockSpec index_map looks up the physical page id and the DMA
engine streams exactly the pages a sequence owns — no gather
materialization. Online softmax accumulates across pages (same lane-
replicated stat layout as flash_attention.py).

Layouts:
    q            [B, H, D]          one decode token per sequence
    k/v_cache    [num_pages, page_size, KVH, D]   (KVH <= H: GQA pools —
                 query heads grouped G = H // KVH over shared KV heads)
    block_tables [B, max_pages]     physical page id per logical page
    context_lens [B]                valid KV length per sequence
Returns o [B, H, D].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
NEG_INF = np.float32(-1e30)


def _grouped(H, KVH):
    """Query-head group size for GQA pools (KVH kv heads shared across H
    query heads); identity when the pool is classic multi-head."""
    if H % KVH:
        raise ValueError(
            f"{H} query heads not divisible by {KVH} KV heads")
    return H // KVH


def paged_attention_reference(q, k_cache, v_cache, block_tables,
                              context_lens, scale=None):
    """jnp formulation (always-correct path; XLA compiles the page gather).
    Shapes as in the module docstring; GQA-aware — the pools may carry
    ``KVH <= H`` KV heads, query heads grouped ``G = H // KVH``."""
    B, H, D = q.shape
    KVH = k_cache.shape[2]
    G = _grouped(H, KVH)
    page_size = k_cache.shape[1]
    scale = np.float32(scale if scale is not None else 1.0 / np.sqrt(D))
    # clamp sentinel-padded ids: OOB take fills NaN, and 0-weight * NaN
    # would poison the output; clamped pages are masked by context_lens
    block_tables = jnp.clip(block_tables, 0, k_cache.shape[0] - 1)
    # gather each sequence's pages: [B, max_pages, page_size, KVH, D]
    k = jnp.take(k_cache, block_tables, axis=0)
    v = jnp.take(v_cache, block_tables, axis=0)
    S = block_tables.shape[1] * page_size
    k = k.reshape(B, S, KVH, D)
    v = v.reshape(B, S, KVH, D)
    qg = q.reshape(B, KVH, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg,
                   k.astype(jnp.float32)) * scale
    valid = jnp.arange(S)[None, :] < context_lens[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    o = o.reshape(B, H, D)
    # a fully-masked row softmaxes to uniform: zero it (context_len == 0)
    o = jnp.where((context_lens > 0)[:, None, None], o, 0.0)
    return o.astype(q.dtype)


def _kernel(blk_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, page_size, groups):
    b = pl.program_id(0)
    i = pl.program_id(1)
    n = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)               # [H, D]
    k = k_ref[0].astype(jnp.float32)               # [page, KVH, D]
    v = v_ref[0].astype(jnp.float32)
    H, D = q.shape
    kvh = H // groups
    kt = jnp.swapaxes(k, 0, 1)                     # [KVH, page, D]
    vt = jnp.swapaxes(v, 0, 1)
    # grouped-query scores: query heads [KVH, G] batch over their shared
    # KV head, then flatten back to the [H, page] stat layout
    qg = q.reshape(kvh, groups, D)
    s = jax.lax.dot_general(
        qg, kt, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(H, -1) * scale
    pos = i * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    in_ctx = pos < len_ref[b]
    s = jnp.where(in_ctx, s, NEG_INF)
    m_prev = m_scr[:]                              # [H, LANES]
    m_new = jnp.maximum(m_prev, jax.lax.broadcast_in_dim(
        s.max(axis=1), m_prev.shape, (0,)))
    # mask explicitly: when every position is masked m_new == NEG_INF and
    # exp(s - m_new) == 1, which would average garbage V pages (a padded
    # block table points anywhere) instead of contributing nothing
    p = jnp.where(in_ctx, jnp.exp(s - m_new[:, :1]),
                  np.float32(0.0))                 # [H, page]
    corr = jnp.exp(m_prev - m_new)
    l_scr[:] = corr * l_scr[:] + jax.lax.broadcast_in_dim(
        p.sum(axis=1), m_prev.shape, (0,))
    pv = jax.lax.dot_general(
        p.reshape(kvh, groups, -1), vt, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(H, D)
    acc_scr[:] = corr[:, :1] * acc_scr[:] + pv
    m_scr[:] = m_new

    @pl.when(i == n - 1)
    def _final():
        o_ref[0] = (acc_scr[:]
                    / jnp.maximum(l_scr[:, :1], np.float32(1e-30))) \
            .astype(o_ref.dtype)


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    scale=None, interpret=False):
    """Pallas kernel: grid (B, max_pages); the k/v BlockSpec index_maps read
    the scalar-prefetched page table, so the DMA streams each sequence's
    physical pages directly."""
    B, H, D = q.shape
    KVH = k_cache.shape[2]
    groups = _grouped(H, KVH)
    num_pages, page_size = k_cache.shape[0], k_cache.shape[1]
    max_pages = block_tables.shape[1]
    scale = np.float32(scale if scale is not None else 1.0 / np.sqrt(D))

    def _page(b, i, blk, ln):
        # clamp: tables padded past context_lens (sentinel -1 or any id)
        # must not drive an out-of-bounds block DMA; the kernel's in_ctx
        # mask already zeroes such pages' contribution
        return (jnp.clip(blk[b, i], 0, num_pages - 1), 0, 0, 0)

    kernel = functools.partial(_kernel, scale=scale, page_size=page_size,
                               groups=groups)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # block_tables, context_lens
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, i, blk, ln: (b, 0, 0)),
            pl.BlockSpec((1, page_size, KVH, D), _page),
            pl.BlockSpec((1, page_size, KVH, D), _page),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, i, blk, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, LANES), jnp.float32),
            pltpu.VMEM((H, LANES), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
            interpret=interpret,
        )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
          q, k_cache, v_cache)


def paged_attention_trainable(q, k_cache, v_cache, block_tables,
                              context_lens, scale=None, interpret=False):
    """Pallas forward + reference-path backward: the scalar-prefetch grid
    spec has no JVP rule, so jax.vjp through the raw kernel raises — this
    custom_vjp keeps the fast forward and differentiates through the
    mathematically-identical gather formulation."""

    @jax.custom_vjp
    def run(q, kc, vc):
        return paged_attention(q, kc, vc, block_tables, context_lens,
                               scale=scale, interpret=interpret)

    def fwd(q, kc, vc):
        return run(q, kc, vc), (q, kc, vc)

    def bwd(res, ct):
        q, kc, vc = res
        _, vjp = jax.vjp(
            lambda a, b, c: paged_attention_reference(
                a, b, c, block_tables, context_lens, scale=scale),
            q, kc, vc)
        return vjp(ct)

    run.defvjp(fwd, bwd)
    return run(q, k_cache, v_cache)
