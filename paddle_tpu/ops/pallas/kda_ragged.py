"""Ragged delta-rule recurrence over a per-request STATE (Kimi Delta
Attention, arXiv:2510.26692) — decode rows and prefill chunks in one
launch.

What a request leaves behind such a layer is not a row a token but ONE
matrix a head, ``S`` ``[d_k, d_v]`` float32, whatever the request's
length. A token of a head decays it channel by channel, corrects it by the
delta rule and reads it:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The states live in a pool ``[slots + 1, H, d_k, d_v]`` held by SLOT (slot
0 is the scrap slot: padded rows read and write it, as page 0 of a page
pool). The launch is the flat token stream of ``ragged_attention.py``:

    q, k, v, alpha   [T, H, d]  float32 (q scaled, q and k normalised,
                     ``alpha = exp(g)`` in (0, 1])
    beta             [T, H]     float32
    state            [slots + 1, H, d_k, d_v]  float32
    row_slots        [R] int32  the pool index of each row's request
    row_starts / row_lens / kv_lens   as ``ragged_attention.py`` takes them
    ->               (o [T, H, d_v] float32, the new pool)

A row whose first token is at position 0 (``kv_len == row_len``: a
request's first chunk, or an evicted request recomputing) starts from a
zero state whatever its slot held.

Two backends, the contract of ``ragged_attention.py``:

* :func:`kda_ragged_reference` — the XLA twin: one ``lax.scan`` over the
  flat stream, a token at a time, float32 at the highest precision. The
  start-up gate's other side and the tests' oracle.
* :func:`kda_ragged` — the Pallas kernel. The stream is cut into **work
  items** of at most ``block_q`` tokens of one row
  (``mla_ragged_attention.work_items``); grid ``(head blocks, items)``. An
  item's state block is addressed through its slot in scalar memory;
  consecutive items of one row name the same block, so the state stays in
  VMEM from a chunk's first token to its last and crosses to HBM once each
  way a row. Inside, a loop over the item's own tokens (1 for a decode
  row) runs the recurrence in its token-by-token form on the vector unit:
  ``k``, ``beta k``, ``q`` and ``alpha`` are turned into columns for the
  head block by one transpose each a token. A decode row is bound by the
  128 KiB of state it reads and writes a head; a chunk row by the
  token-by-token form's arithmetic (the chunked form through the matrix
  unit is ROADMAP B5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mla_ragged_attention import work_items
from .ragged_attention import ragged_row_index

__all__ = ["kda_ragged", "kda_ragged_reference"]

F32 = jnp.float32
HEAD_BLOCK = 8       # heads an item works on: one (8, 128) float32 tile


def kda_ragged_reference(q, k, v, alpha, beta, state, row_slots, row_starts,
                         row_lens, kv_lens):
    """The XLA twin: the flat stream a token at a time. Pad tokens read
    and write the scrap slot and come back zeroed."""
    T = q.shape[0]
    rid, pos, valid = ragged_row_index(row_starts, row_lens, kv_lens, T)
    slot = jnp.where(valid, row_slots.astype(jnp.int32)[rid], 0)
    beta = beta.astype(F32)[..., None]
    xs = (slot, pos, q.astype(F32), k.astype(F32), beta * k.astype(F32),
          beta * v.astype(F32), alpha.astype(F32))

    def token(pool, x):
        s, p, qt, kt, kb, vb, at = x
        S = jnp.where(p == 0, 0.0, pool[s])                 # [H, dk, dv]
        A = S * at[:, :, None]
        u = vb - jnp.einsum("hk,hkv->hv", kb, A, precision="highest")
        S = A + kt[:, :, None] * u[:, None, :]
        o = jnp.einsum("hk,hkv->hv", qt, S, precision="highest")
        return pool.at[s].set(S), o

    state, o = jax.lax.scan(token, state.astype(F32), xs)
    return jnp.where(valid[:, None, None], o, 0.0), state


def _kernel(slot_ref, nq_ref, first_ref, zero_ref, tok_ref, s_in_ref,
            o_ref, s_ref, *, heads):
    w = pl.program_id(1)

    # the state block is the item's slot: resident while consecutive
    # items name the same one, so only a row's first item loads it
    @pl.when(zero_ref[w] == 1)
    def _():
        s_ref[0] = jnp.zeros(s_ref.shape[1:], F32)

    @pl.when((first_ref[w] == 1) & (zero_ref[w] == 0))
    def _():
        s_ref[0] = s_in_ref[0]

    def token(j, carry):
        slab = tok_ref[0, j]                    # [5, heads, d]
        # columns for the head block: [d, heads], one transpose a plane
        kT, kbT, qT, aT = (slab[i].T for i in range(4))
        vb = slab[4]
        rows = []
        for h in range(heads):
            A = s_ref[0, h] * aT[:, h:h + 1]
            u = vb[h:h + 1] - jnp.sum(A * kbT[:, h:h + 1], axis=0,
                                      keepdims=True)
            S = A + kT[:, h:h + 1] * u
            s_ref[0, h] = S
            rows.append(jnp.sum(S * qT[:, h:h + 1], axis=0, keepdims=True))
        o_ref[0, j] = jnp.concatenate(rows, axis=0)
        return carry

    jax.lax.fori_loop(0, nq_ref[w], token, 0)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def _call(q, k, v, alpha, beta, state, row_slots, row_starts, row_lens,
          kv_lens, *, block_q, interpret):
    T, H, D = q.shape
    if v.shape[-1] != D:
        raise ValueError("the kernel packs a token's k, q, alpha and v in "
                         f"one slab: d_k {D} must equal d_v {v.shape[-1]}")
    hb = min(HEAD_BLOCK, H)
    if H % hb:
        raise ValueError(f"{H} heads are no whole blocks of {hb}")
    row, pos0, nq, tok = work_items(row_starts, row_lens, kv_lens, T,
                                    block_q)
    n_items = row.shape[0]
    rl, kl = row_lens.astype(jnp.int32), kv_lens.astype(jnp.int32)
    used = nq > 0
    # an unused item zeroes the scrap slot: it has to write its block
    first = jnp.where(used, pos0 == (kl - rl)[row], True)
    zero = jnp.where(used, pos0 == 0, True)
    slot = jnp.where(used, row_slots.astype(jnp.int32)[row], 0)
    b = beta.astype(F32)[..., None]
    planes = jnp.stack([k.astype(F32), b * k.astype(F32), q.astype(F32),
                        alpha.astype(F32), b * v.astype(F32)], axis=1)
    planes = jnp.concatenate([planes, jnp.zeros((1, 5, H, D), F32)])
    slabs = planes[tok]                       # [items, block_q, 5, H, D]
    kernel = functools.partial(_kernel, heads=hb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # slot, tokens, first, zero
        grid=(H // hb, n_items),
        in_specs=[
            pl.BlockSpec((1, block_q, 5, hb, D),
                         lambda g, w, *_: (w, 0, 0, g, 0)),
            pl.BlockSpec((1, hb) + state.shape[2:],
                         lambda g, w, slot, *_: (slot[w], g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hb, D),
                         lambda g, w, *_: (w, 0, g, 0)),
            pl.BlockSpec((1, hb) + state.shape[2:],
                         lambda g, w, slot, *_: (slot[w], g, 0, 0)),
        ],
    )
    with jax.enable_x64(False):
        out, state = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((n_items, block_q, H, D), F32),
                       jax.ShapeDtypeStruct(state.shape, F32)],
            # the pool is updated in place: operand 5 (after the four
            # scalar arrays and the slabs) is output 1
            input_output_aliases={5: 1},
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=64 * 2 ** 20),
            # the HLO instruction's name, hence the device trace's: the
            # benchmark's per-kernel metrics find the kernel by this prefix
            name="kda_ragged",
        )(slot, nq, first.astype(jnp.int32), zero.astype(jnp.int32), slabs,
          state.astype(F32))
    # back to the flat stream; unused slots of an item land on a spare row
    flat = jnp.zeros((T + 1, H, D), F32)
    flat = flat.at[tok.reshape(-1)].set(out.reshape(-1, H, D))
    return flat[:T], state


def kda_ragged(q, k, v, alpha, beta, state, row_slots, row_starts, row_lens,
               kv_lens, interpret=False):
    """The Pallas kernel (module docstring). Work items hold 1 token where
    the launch has no more tokens than rows (a decode round), else 8
    (5.4 ms against 5.7 at 16 and 7.8 at 64 for 127 decode rows and a
    512-token chunk on a v5e: a decode row's item copies a whole block).
    Jitted inside, so every layer of a model shares one traced kernel.
    -> ``(o [T, H, d_v], the new pool)``, pad tokens zeroed."""
    return _call(q, k, v, alpha, beta, state, row_slots, row_starts,
                 row_lens, kv_lens,
                 block_q=1 if q.shape[0] <= row_starts.shape[0] else 8,
                 interpret=bool(interpret))
