"""Ragged delta-rule recurrence over a per-request STATE (Kimi Delta
Attention, arXiv:2510.26692) — decode rows and prefill chunks of one
round.

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
* :func:`kda_ragged` — the Pallas kernels. **Which row takes which form
  is read off the row's length**; both forms keep a row's state in VMEM
  from its first token to its last, so it crosses to HBM once each way a
  row, and both launches update the one pool in place on disjoint slots.

  **A row of one token** (a decode row) takes the recurrence in its
  token-by-token form on the vector unit (``kda_ragged_tokens``): one work
  item a row, grid ``(head blocks, rows)``, the item's state block
  addressed through its slot in scalar memory; ``k``, ``beta k``, ``q``
  and ``alpha`` are turned into columns for the head block by one
  transpose each. It is bound by the 128 KiB of state it reads and writes
  a head, and an item that copies one token's operands is the cheapest
  there is: a decode row must not pay for a chunk row's item size.

  **A row of several tokens** (a prefill chunk) takes the CHUNKED form
  through the matrix unit (``kda_ragged_chunks``; section 3 of the paper).
  The row is cut into blocks of ``CHUNK`` = 64 tokens. With ``S_0`` the
  state before the block, ``Gamma_t`` the decay from the block's start to
  token ``t`` and ``D(t, j) = Gamma_t / Gamma_j`` (a vector over the key
  channels, at most 1 for ``j <= t``) the block is

      A[t, j] = beta_t sum_c k_t[c] k_j[c] D(t, j)[c]     (j <  t)
      B[t, j] =        sum_c q_t[c] k_j[c] D(t, j)[c]     (j <= t)
      (I + A) U = beta (V - (K Gamma) S_0)
      O   = (Q Gamma) S_0 + B U
      S_C = Diag(Gamma_C) S_0 + (K D(C, .))^T U

  the same sums as the token form in another order: ``[128, 128] x [128,
  128]``, ``[64, 64] x [64, 128]`` and ``[128, 64] x [64, 128]`` products
  of float32 operands at the highest precision, float32 sums. ``A`` and
  ``B`` are products of a row factor and a column factor, and **no
  factor may leave float32's range**: a log-decay is in (-5, 0) a token
  (the safe gate's published lower bound), so a block's cumulated decay
  reaches exp(-320) and dividing by it overflows. Decays are therefore
  cumulated only within **sub-blocks of ``SUB`` = 16 tokens**, as running
  products of ``alpha`` (no logarithm is taken, so a decay is as exact as
  the token form's): ``P_t`` in [exp(-80), 1] from its sub-block's start
  to ``t`` and ``1 / P_t`` at most exp(80) = 5.5e34, inside float32 —
  **16 x 5 = 80 is what the published bound allows; 32 tokens would reach
  exp(160)**. The matrix unit takes a float32 operand as three bfloat16
  parts, the smallest 2^-16 of it, and flushes what falls under 1.2e-38:
  a factor near exp(-80) would reach it as its leading part alone (7e-4
  on the outputs with every gate at the bound, on the chip). So the two
  factors of a sub-block's own triangle meet at its MIDDLE: the row
  factor is ``P_t / P_mid``, the column factor ``P_mid / P_j``, both
  within exp(+-40). Across sub-blocks the column factor decays ``k_j``
  to the middle of ``t``'s sub-block as well (at most 1), so each of a
  block's four sub-block rows has its own column operand. ``(I + A)^-1``
  is applied by forward substitution: 15 rank-one steps a 16-token
  sub-block on the vector unit (the recurrence's own order, stable
  whatever the keys), the sub-blocks below it through the matrix unit.

  One grid step a head block runs a loop over exactly the blocks the
  launch's chunk rows have (no unused items), fetching a block's
  operands by DMA at its token offset behind the block before it. A
  partial last block is padded by tokens that do nothing (``alpha`` 1,
  all else 0). The kernel is written to be SHORT: every operation takes
  the head block's 8 heads at once (``[8, 64, 128]`` operands, products
  batched by head) and the solve's sub-blocks are one loop over VMEM
  buffers. A round program is traced and lowered for every token pad of
  every start, whatever the compile cache holds, and written a head and
  a sub-block at a time (5,000 operations against 425) the kernel ran as
  fast and cost each of them 25 s on the serving host.

  Every launch makes both launches, whatever its shape: the row's length
  is the one rule. In a decode round the chunk launch finds no block and
  costs 6 us a layer on the chip (128 decode rows: 1.139 ms against 1.134
  without it; PERF.md section 6, PR 36).
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
CHUNK = 64           # tokens a block of the chunked form
SUB = 16             # tokens a sub-block: exp(16 x 5) is inside float32
PLANES = 5           # k, beta k, q, alpha, beta v


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


# ------------------------------------------------- the token-by-token form

def _token_kernel(slot_ref, used_ref, zero_ref, tok_ref, s_in_ref, o_ref,
                  s_ref, *, heads):
    w = pl.program_id(1)

    # one item a row: the state block is the row's slot, in and out
    @pl.when(zero_ref[w] == 1)
    def _():
        s_ref[0] = jnp.zeros(s_ref.shape[1:], F32)

    @pl.when(zero_ref[w] == 0)
    def _():
        s_ref[0] = s_in_ref[0]

    @pl.when(used_ref[w] == 1)
    def _():
        slab = tok_ref[0, 0]                    # [5 * heads, d]
        # columns for the head block: [d, heads], one transpose a plane
        kT, kbT, qT, aT = (slab[i * heads:(i + 1) * heads].T
                           for i in range(4))
        vb = slab[4 * heads:]
        rows = []
        for h in range(heads):
            A = s_ref[0, h] * aT[:, h:h + 1]
            u = vb[h:h + 1] - jnp.sum(A * kbT[:, h:h + 1], axis=0,
                                      keepdims=True)
            S = A + kT[:, h:h + 1] * u
            s_ref[0, h] = S
            rows.append(jnp.sum(S * qT[:, h:h + 1], axis=0, keepdims=True))
        o_ref[0] = jnp.concatenate(rows, axis=0)


def _token_launch(planes, state, slot, used, zero, tok, *, heads, interpret):
    """One item a row of one token: ``tok`` [items] indexes ``planes``
    ``[tokens, G, 5 * heads, D]`` (an unused item names a spare row and
    zeroes the scrap slot: it has to write its block).
    -> (o [items, H, D], the pool)."""
    G, D = planes.shape[1], planes.shape[-1]
    n_items = tok.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # slot, used, zero
        grid=(G, n_items),
        in_specs=[
            pl.BlockSpec((1, 1, PLANES * heads, D),
                         lambda g, w, *_: (w, g, 0, 0)),
            pl.BlockSpec((1, heads) + state.shape[2:],
                         lambda g, w, slot, *_: (slot[w], g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, D), lambda g, w, *_: (w, g, 0)),
            pl.BlockSpec((1, heads) + state.shape[2:],
                         lambda g, w, slot, *_: (slot[w], g, 0, 0)),
        ],
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_token_kernel, heads=heads),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((n_items, G * heads, D), F32),
                       jax.ShapeDtypeStruct(state.shape, F32)],
            # the pool is updated in place: operand 4 (after the three
            # scalar arrays and the slabs) is output 1
            input_output_aliases={4: 1},
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=64 * 2 ** 20),
            # the HLO instruction's name, hence the device trace's: the
            # benchmark's per-kernel metrics find the kernels by
            # ``kda_ragged``
            name="kda_ragged_tokens",
        )(slot, used.astype(jnp.int32), zero.astype(jnp.int32), planes[tok],
          state)


# --------------------------------------------------------- the chunked form

def _dot(a, b, contract=((2,), (1,))):
    """``[h, m, k] x [h, k, n]``, a product a head through the matrix unit
    at float32 precision."""
    return jax.lax.dot_general(a, b, (contract, ((0,), (0,))),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def _tokens(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _chunk_block(K, Kb, Q, Al, Vb, S0, idx, below, upto, under, bufs):
    """One block of a head block in the chunked form (module docstring),
    every head at once. ``K``, ``Kb`` = beta k, ``Q``, ``Al``, ``Vb`` =
    beta v: [H, C, D]; ``S0`` [H, D, D]; ``idx`` [H * C, D] a token's place
    in its sub-block; ``below`` / ``upto`` / ``under`` [C, C] the strictly
    lower triangle, the lower triangle and what lies under the diagonal
    sub-blocks; ``bufs`` the solve's VMEM ([H, C, C], [H, C, SUB],
    [H, C, D]). -> (O [H, C, D], S_C)."""
    H, C, D = K.shape
    nb = C // SUB

    def sub(x, a):
        return x[:, a * SUB:(a + 1) * SUB]

    # P: the decay from a sub-block's start to each of its tokens, a scan
    # of running products down the tokens (Hillis-Steele, log2(SUB) steps;
    # the heads one under the other, a sub-block never spans two)
    P, s = Al.reshape(H * C, D), 1
    while s < SUB:
        P = P * jnp.where(idx >= s, pltpu.roll(P, s, 0), 1.0)
        s *= 2
    P = P.reshape(H, C, D)
    invP = 1.0 / P                                  # at most exp(80)
    E = [sub(P, a)[:, SUB - 1:] for a in range(nb)]     # a sub-block's whole
    # ... and to and from a sub-block's MIDDLE, where the row and column
    # factors of its own triangle meet: both within exp(+-40)
    at = slice(SUB // 2 - 1, SUB // 2)
    mid = [sub(P, a)[:, at] for a in range(nb)]
    to_mid = [sub(invP, a) * mid[a] for a in range(nb)]
    from_mid = [sub(P, a) * sub(invP, a)[:, at] for a in range(nb)]
    # Gamma: the decay from the block's start to each token
    gam, run = [], None
    for a in range(nb):
        gam.append(sub(P, a) if run is None else sub(P, a) * run)
        run = E[a] if run is None else run * E[a]
    gam, whole = _tokens(gam), run
    X = _dot(jnp.concatenate([Kb * gam, Q * gam], axis=1), S0)
    rhs, qs = Vb - X[:, :C], X[:, C:]
    # each key decayed to the END of its own sub-block (at most 1)
    Ke = [sub(K, a) * (E[a] * sub(invP, a)) for a in range(nb)]
    A, B = [], []
    for a in range(nb):
        # the column operand of sub-block row a: keys of earlier
        # sub-blocks decayed to a's middle, a's own from it, none after
        cols, run = [], mid[a]
        for b in range(a - 1, -1, -1):
            cols.insert(0, Ke[b] * run)
            run = run * E[b]
        cols.append(sub(K, a) * to_mid[a])
        if a + 1 < nb:
            cols.append(jnp.zeros((H, C - (a + 1) * SUB, D), F32))
        M = _dot(jnp.concatenate([sub(Kb, a) * from_mid[a],
                                  sub(Q, a) * from_mid[a]], axis=1),
                 _tokens(cols), ((2,), (2,)))       # [H, 2 SUB, C]
        A.append(M[:, :SUB])
        B.append(M[:, SUB:])
    A, B = _tokens(A), jnp.where(upto, _tokens(B), 0.0)
    # (I + A) U = rhs by forward substitution, a sub-block at a time: one
    # loop, traced once, over buffers it can cut at a sub-block it does
    # not know. ``a_ref``: A under the diagonal sub-blocks; ``d_ref``:
    # the diagonal sub-blocks' own triangles; ``u_ref``: rhs, a sub-block
    # after the other overwritten by U (what ``a_ref`` multiplies of the
    # rest is zero)
    a_ref, d_ref, u_ref = bufs
    a_ref[...] = jnp.where(under, A, 0.0)
    low = jnp.where(below, A, 0.0)
    d_ref[...] = _tokens([sub(low, a)[:, :, a * SUB:(a + 1) * SUB]
                          for a in range(nb)])
    u_ref[...] = rhs

    def solve(a, carry):
        rows = pl.ds(pl.multiple_of(a * SUB, SUB), SUB)
        x = u_ref[:, rows, :] - _dot(a_ref[:, rows, :], u_ref[...])
        Ad = d_ref[:, rows, :]
        for i in range(SUB - 1):
            x = x - Ad[:, :, i:i + 1] * x[:, i:i + 1]
        u_ref[:, rows, :] = x
        return carry

    jax.lax.fori_loop(0, nb, solve, 0)
    U = u_ref[...]
    O = qs + _dot(B, U)
    # each key decayed to the block's end
    kf, run = [None] * nb, None
    for a in range(nb - 1, -1, -1):
        kf[a] = Ke[a] if run is None else Ke[a] * run
        run = E[a] if run is None else run * E[a]
    # the block's whole decay [H, 1, D] as columns [H, D, 1]
    whole = jnp.swapaxes(jnp.broadcast_to(whole, (H, 8, D)), 1, 2)[:, :, :1]
    return O, whole * S0 + _dot(_tokens(kf), U, ((1,), (1,)))


def _chunk_kernel(n_ref, start_ref, nq_ref, slot_ref, first_ref, zero_ref,
                  last_ref, planes_ref, s_in_ref, o_in_ref, o_ref, s_ref,
                  tok_buf, o_buf, s_buf, a_buf, d_buf, u_buf, sem, *, heads,
                  block):
    del o_in_ref                    # the zeroed stream o_ref starts as
    g = pl.program_id(0)
    n = n_ref[0]
    hs = pl.ds(g * heads, heads)
    D = s_buf.shape[-1]

    def fetch(w, b):
        return pltpu.make_async_copy(
            planes_ref.at[pl.ds(start_ref[w], block), g], tok_buf.at[b],
            sem.at[b])

    @pl.when(n > 0)
    def _():
        fetch(0, 0).start()

    tok_i = jax.lax.broadcasted_iota(jnp.int32, (block, D), 0)
    idx = jax.lax.broadcasted_iota(jnp.int32, (heads * block, D), 0) % SUB
    r = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    below, upto, under = c < r, c <= r, c // SUB < r // SUB

    def item(w, carry):
        b = w % 2

        @pl.when(w + 1 < n)
        def _():
            fetch(w + 1, 1 - b).start()

        @pl.when(zero_ref[w] == 1)
        def _():
            s_buf[...] = jnp.zeros(s_buf.shape, F32)

        @pl.when((first_ref[w] == 1) & (zero_ref[w] == 0))
        def _():
            cp = pltpu.make_async_copy(s_in_ref.at[slot_ref[w], hs], s_buf,
                                       sem.at[2])
            cp.start()
            cp.wait()

        fetch(w, b).wait()
        live = tok_i < nq_ref[w]

        def plane(i, pad):
            """Plane ``i`` of the block, heads first: [heads, block, D];
            the tokens past the item's own do nothing."""
            x = tok_buf[b, :, i * heads:(i + 1) * heads, :]
            return jnp.where(live, jnp.swapaxes(x, 0, 1), pad)

        O, S = _chunk_block(plane(0, 0.0), plane(1, 0.0), plane(2, 0.0),
                            plane(3, 1.0), plane(4, 0.0), s_buf[...], idx,
                            below, upto, under, (a_buf, d_buf, u_buf))
        o_buf[...] = jnp.swapaxes(O, 0, 1)
        s_buf[...] = S
        # a partial block's tail lands on the stream behind the row: zeros
        # that the rows behind it, each after this one, overwrite
        cp = pltpu.make_async_copy(
            o_buf, o_ref.at[pl.ds(start_ref[w], block), hs], sem.at[3])
        cp.start()
        cp.wait()

        @pl.when(last_ref[w] == 1)
        def _():
            cp = pltpu.make_async_copy(s_buf, s_ref.at[slot_ref[w], hs],
                                       sem.at[2])
            cp.start()
            cp.wait()

        return carry

    jax.lax.fori_loop(0, n, item, 0)


def _chunk_launch(planes, state, items, *, heads, block, interpret):
    """Every block of the launch's chunk rows. ``items``: how many blocks
    there are [1] and, a block, its first token in the stream, its token
    count, its row's slot and whether it is its row's first block, starts
    from zero, is its row's last (each [n], the used ones first);
    ``planes`` ``[T + block, G, 5 * heads, D]``. -> (o [T + block, H, D],
    zero where no such row has a token; the pool)."""
    Tp, G, _, D = planes.shape
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(items),
        grid=(G,),
        in_specs=[any_, any_, any_],
        out_specs=[any_, any_],
        scratch_shapes=[
            pltpu.VMEM((2, block, PLANES * heads, D), F32),
            pltpu.VMEM((block, heads, D), F32),
            pltpu.VMEM((heads,) + state.shape[2:], F32),
            pltpu.VMEM((heads, block, block), F32),
            pltpu.VMEM((heads, block, SUB), F32),
            pltpu.VMEM((heads, block, D), F32),
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_chunk_kernel, heads=heads, block=block),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((Tp, G * heads, D), F32),
                       jax.ShapeDtypeStruct(state.shape, F32)],
            # in place: the pool (operand 8 after the seven scalar arrays
            # and the planes) is output 1, the zeroed stream output 0
            input_output_aliases={len(items) + 1: 1, len(items) + 2: 0},
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 2 ** 20),
            name="kda_ragged_chunks",
        )(*items, planes, state, jnp.zeros((Tp, G * heads, D), F32))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _call(q, k, v, alpha, beta, state, row_slots, row_starts, row_lens,
          kv_lens, *, block, interpret):
    """Rows of one token in the token form, one item a row; longer rows
    in the chunked form in blocks of ``block`` tokens."""
    T, H, D = q.shape
    if v.shape[-1] != D:
        raise ValueError("the kernel packs a token's k, q, alpha and v in "
                         f"one slab: d_k {D} must equal d_v {v.shape[-1]}")
    hb = min(HEAD_BLOCK, H)
    if H % hb:
        raise ValueError(f"{H} heads are no whole blocks of {hb}")
    if block % SUB:
        raise ValueError(f"a block of {block} tokens is no whole "
                         f"sub-blocks of {SUB}")
    rs, rl, kl = (a.astype(jnp.int32) for a in (row_starts, row_lens,
                                                kv_lens))
    slots = row_slots.astype(jnp.int32)
    b = beta.astype(F32)[..., None]
    planes = jnp.stack([k.astype(F32), b * k.astype(F32), q.astype(F32),
                        alpha.astype(F32), b * v.astype(F32)], axis=1)
    # [T, 5, H, D] -> [T, head blocks, 5 * hb, D], spare rows behind
    planes = planes.reshape(T, PLANES, H // hb, hb, D).swapaxes(1, 2) \
        .reshape(T, H // hb, PLANES * hb, D)
    planes = jnp.pad(planes, ((0, block), (0, 0), (0, 0), (0, 0)))
    crow, cpos0, cnq, ctok = work_items(
        rs, jnp.where(rl > 1, rl, 0), kl, T, block)
    cused = cnq > 0
    items = (jnp.sum(cused, dtype=jnp.int32)[None],
             jnp.where(cused, ctok[:, 0], T), cnq,
             jnp.where(cused, slots[crow], 0),
             cused & (cpos0 == (kl - rl)[crow]),
             cused & (cpos0 == 0),
             cused & (cpos0 + cnq == kl[crow]))
    flat, state = _chunk_launch(
        planes, state.astype(F32),
        tuple(a.astype(jnp.int32) for a in items), heads=hb, block=block,
        interpret=interpret)
    used = rl == 1
    tok = jnp.where(used, rs, T)
    out, state = _token_launch(
        planes, state, jnp.where(used, slots, 0), used,
        jnp.where(used, kl == 1, True), tok, heads=hb, interpret=interpret)
    # back to the flat stream; unused items land on a spare row
    return flat.at[tok].set(out)[:T], state


def kda_ragged(q, k, v, alpha, beta, state, row_slots, row_starts, row_lens,
               kv_lens, interpret=False):
    """The Pallas kernels (module docstring): a launch's rows of one token
    through the token form and its longer rows through the chunked form,
    two launches over the one pool. Jitted inside, so every layer of a
    model shares one traced kernel.
    -> ``(o [T, H, d_v], the new pool)``, pad tokens zeroed."""
    return _call(q, k, v, alpha, beta, state, row_slots, row_starts,
                 row_lens, kv_lens, block=CHUNK, interpret=bool(interpret))
