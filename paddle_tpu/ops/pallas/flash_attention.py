"""Flash attention — Pallas TPU kernel (forward + backward).

Reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu (FlashAttention-2 CUDA
kernels, dynloaded from third_party/flashattn). TPU-native rebuild: online-
softmax tiling in VMEM with the MXU doing the block matmuls; the backward
recomputes P blockwise from the saved logsumexp (FA-2 style) instead of
storing the S×S matrix — O(S) memory for any sequence length.

Layout: [B, H, S, D] inside the kernels (the functional layer transposes from
paddle's [B, S, H, D]). D ≤ 128; S is padded to the schedule's blocks.

How the blocks are chosen. ``block_schedule`` is a pure function of what a
call can see — the two sequence lengths, the head dimension, the operand
dtype — and returns one :class:`Schedule`: the padded lengths, and for each
of the three kernels a ``(block_q, block_k, chunk)`` triple. ``block_q`` x ``block_k`` is what one grid step fetches; inside the
step the block's queries are worked through ``chunk`` rows at a time, so
the float32 temporaries (scores, probabilities, their gradients) are
[chunk, block_k] whatever the block, and VMEM bounds the operand blocks
only. A sequence shorter than 128 is one block of its own length (at least
8); a longer one is cut into the fewest equal blocks of a multiple of 128
that stay under ``BLOCK_CAP`` and under the VMEM budget stated beside it.
At the shape that decided both constants — [32, 2048, 128] bf16, causal, on
a v5e — a grid step costs 0.36 us before it computes anything, and the
reductions across lanes cost a chunk's row the same whatever its width, so
few steps and wide chunks win: one 2048 x 2048 step a head in chunks of 256
where 128 x 128 blocks took 256 steps (PERF.md section 6, PR 28).
``block_q`` / ``block_k`` given to ``flash_attention_bshd`` override all
three kernels. There is no environment variable, flag or tuning table.

What a step feeds the matrix unit. ``q kT`` and ``dO vT`` take the stored
operands as they are (``preferred_element_type=float32``: a bf16 x bf16
product is exact in float32). ``p`` and ``ds`` are rounded to the operand
dtype only as the operand of the product that consumes them. The running
max and sum, ``lse``, ``delta``, the masks and every accumulator are
float32; with float32 operands nothing is rounded anywhere.

What a skipped step fetches: nothing. Under a causal mask the index maps of
the streamed operands are clamped to the last key block a query block sees
(the first query block that sees a key block, in the dk/dv kernel), so a
dead step repeats the previous block index and the pipeline issues no copy.
A mask is built only in the blocks the diagonal or the keys' padding
crosses; on a square block's own diagonal each chunk of queries stops at
the last key it sees, so the half above the diagonal is never computed.

Each ``pallas_call`` carries a ``name=``: it becomes the HLO instruction's
name (through ``jax.checkpoint``, its rematerialised copy and ``shard_map``
alike), so a device trace reads ``flash_attention_fwd``,
``flash_attention_bwd_dq`` and ``flash_attention_bwd_dkv``. The benchmark's
per-kernel metrics find the kernels by the prefix ``flash_attention``:
whatever implements training attention on the hot path keeps it. With the
trace buffer on (``observability/tracing.py``), each traced call records one
``flash_attention.schedule`` event (``cat`` ``kernels``) that says which
schedule the program was compiled with.
"""
from __future__ import annotations

import functools
import math
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability import tracing as _trc

# np.float32 constants: paddle_tpu enables jax_enable_x64, and a bare python
# float inside the kernel materializes as an f64 constant that Mosaic cannot
# legalize (tpu.truncf f64->f32).
NEG_INF = np.float32(-1e30)


def _no_x64():
    """Mosaic cannot legalize the i64 index arithmetic jax_enable_x64
    produces (even a trivial kernel fails func.return legalization), so every
    pallas_call traces under an x64-disabled scope. Inputs/outputs are
    explicit f32/bf16 arrays, so results are unaffected."""
    return jax.enable_x64(False)
# Mosaic requires the minor (lane) dim of every VMEM block to be 128-aligned
# or equal to the array dim, so per-row stats (m/l/lse/delta) are carried
# replicated across 128 lanes (same convention as
# jax/experimental/pallas/ops/tpu/flash_attention.py MIN_BLOCK_SIZE). The
# dk/dv kernel works on the transposed scores and reads lse/delta with the
# sequence on the lanes instead: [BH, 1, SQ].
LANES = 128

NT = (((1,), (1,)), ((), ()))     # a @ b.T
NN = (((1,), (0,)), ((), ()))     # a @ b


# ---------------- the schedule ----------------
class Schedule(NamedTuple):
    """Padded lengths and, per kernel, ``(block_q, block_k, chunk)``."""
    sq: int
    sk: int
    fwd: tuple
    dq: tuple
    dkv: tuple


# What one grid step may hold in VMEM: the double-buffered operand blocks,
# the scratch accumulators and the float32 [chunk, block_k] temporaries
# (scores, probabilities, their gradients). A v5e core has 128 MiB; a step
# that needs more than the compiler's default scoped limit (16 MiB) is given
# twice its estimate as ``vmem_limit_bytes``, and the schedule halves its
# cap before the estimate passes this budget.
VMEM_BUDGET = 40 * 2 ** 20
_SCOPED_DEFAULT = 16 * 2 ** 20
# The largest block of either axis and the chunk of queries, for all three
# kernels; chosen on the chip from the kernels' times in a trace (PERF.md
# section 6, PR 28)
BLOCK_CAP = 2048
CHUNK = 256
KERNELS = ("fwd", "dq", "dkv")
# float32 [chunk, block_k] temporaries alive at once
_TILES = {"fwd": 3, "dq": 4, "dkv": 4}


def _step_vmem_bytes(kernel, block_q, block_k, chunk, d, itemsize):
    """Bytes of VMEM one grid step of ``kernel`` needs, by the count above."""
    q_side = 2 if kernel == "fwd" else 3          # q (+ dO) in, o / dq out
    k_side = 2 if kernel != "dkv" else 4          # k, v in (+ dk, dv out)
    blocks = 2 * d * itemsize * (q_side * block_q + k_side * block_k)
    stats = 2 * 2 * block_q * LANES * 4           # lse, delta / the lse out
    scratch = 4 * {"fwd": block_q * (d + 2 * LANES), "dq": block_q * d,
                   "dkv": 2 * block_k * d}[kernel]
    tiles = _TILES[kernel] * min(chunk, block_q) * block_k * 4
    return blocks + stats + scratch + tiles


def _cut(s, cap):
    """-> (block, padded length): ``s`` cut into the fewest equal blocks of
    a multiple of 128 no larger than ``cap``; a sequence under 128 is one
    block of its own length (at least 8)."""
    if s < LANES:
        b = max(s, 8)
        return b, b
    n128 = pl.cdiv(s, LANES)
    n = pl.cdiv(n128 * LANES, cap)
    b = pl.cdiv(n128, n) * LANES
    return b, n * b


def _chunk(block_q):
    """The query chunk of a block: ``CHUNK`` or 128 where one divides the
    block, else the whole block."""
    return next((c for c in (CHUNK, LANES) if block_q % c == 0), block_q)


def block_schedule(sq, sk, d, dtype):
    """The tile schedule of one call, from its shapes alone: today the same
    blocks for all three kernels (a causal mask changes nothing: its dead
    steps are few at these sizes, and fetch nothing)."""
    itemsize = jnp.dtype(dtype).itemsize
    cap = BLOCK_CAP
    while cap > LANES and VMEM_BUDGET < max(
            _step_vmem_bytes(kernel, cap, cap, CHUNK, d, itemsize)
            for kernel in KERNELS):
        cap //= 2
    (bq, sq_pad), (bk, sk_pad) = _cut(sq, cap), _cut(sk, cap)
    blocks = (bq, bk, _chunk(bq))
    return Schedule(sq_pad, sk_pad, blocks, blocks, blocks)


def _forced_schedule(sq, sk, block_q, block_k):
    """The explicit override: one block pair for all three kernels, each
    block worked through whole."""
    blocks = (block_q, block_k, block_q)
    return Schedule(pl.cdiv(sq, block_q) * block_q,
                    pl.cdiv(sk, block_k) * block_k, blocks, blocks, blocks)


def grid_steps(schedule, causal, bh=1):
    """-> {kernel: (live, dead)} grid steps of a call with ``bh`` heads: a
    dead step is one the causal mask empties (it fetches and computes
    nothing)."""
    out = {}
    for kernel in KERNELS:
        bq, bk, _ = getattr(schedule, kernel)
        nq, nk = schedule.sq // bq, schedule.sk // bk
        live = sum(1 for i in range(nq) for kb in range(nk)
                   if not causal or kb * bk <= i * bq + bq - 1)
        out[kernel] = (bh * live, bh * (nq * nk - live))
    return out


def _record_schedule(tr, shape, sk, dtype, causal, schedule):
    """One ``flash_attention.schedule`` event in the trace buffer ``tr``:
    called at trace time, once per traced call, behind the caller's gate."""
    steps = grid_steps(schedule, causal, shape[0] * shape[2])
    tr.add("flash_attention.schedule", time.time(), 0.0, cat="kernels", args={
        "shape": list(shape), "sk": sk, "dtype": jnp.dtype(dtype).name,
        "causal": bool(causal), "padded": [schedule.sq, schedule.sk],
        "fwd": list(schedule.fwd), "bwd_dq": list(schedule.dq),
        "bwd_dkv": list(schedule.dkv),
        "steps_live": {k: v[0] for k, v in steps.items()},
        "steps_dead": {k: v[1] for k, v in steps.items()}})


def _compiler_params(kernel, block_q, block_k, chunk, d, dtype):
    need = _step_vmem_bytes(kernel, block_q, block_k, chunk, d,
                            jnp.dtype(dtype).itemsize)
    limit = None if 2 * need <= _SCOPED_DEFAULT else \
        min(2 * need, 100 * 2 ** 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=limit)


# ---------------- which body a step runs ----------------
def _run_step(compute, *, causal, padded, block_q, block_k, i, kb, nk):
    """Run the body step (i, kb) needs, or none: ``compute("plain")`` where
    every score of the block counts, ``compute("masked")`` where the
    diagonal or the keys' padding crosses it, ``compute("diagonal")`` on a
    square block's own diagonal, whose chunks of queries stop at the keys
    they see. A body no step can need is not emitted at all."""
    last = kb == nk - 1           # the block the keys' padding is in
    if not causal:
        cases = {"masked": last, "plain": ~last} if padded else \
            {"plain": True}
    elif block_q == block_k:
        below = kb < i
        cases = {"diagonal": kb == i, "plain": below}
        if padded:
            cases.update(masked=below & last, plain=below & ~last)
    else:
        live = kb * block_k <= i * block_q + block_q - 1
        crossed = kb * block_k + block_k - 1 > i * block_q
        if padded:
            crossed |= last
        cases = {"masked": live & crossed, "plain": live & ~crossed}
    for mode, when in cases.items():
        if when is True:
            compute(mode)
        else:
            pl.when(when)(functools.partial(compute, mode))


def _pieces(mode, block_q, block_k, chunk):
    """The (query rows, key rows) slices of its block a body works through:
    one chunk of queries after another, each against the block's keys — on
    the diagonal, against the keys up to the chunk's own end. The float32
    temporaries are [chunk, keys], whatever the block."""
    return [(slice(c, c + chunk),
             slice(0, c + chunk if mode == "diagonal" else block_k))
            for c in range(0, block_q, chunk)]


def _valid(causal, kv_len, sk, shape, q0, k0, q_axis):
    """The mask of one [rows, cols] piece whose queries start at ``q0`` and
    keys at ``k0``; queries run along ``q_axis``."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    valid = None
    if kv_len < sk:
        valid = kpos < kv_len
    if causal:
        valid = (kpos <= qpos) if valid is None else valid & (kpos <= qpos)
    return valid


def _lanes(x, n):
    """A lane-replicated [R, LANES] statistic as [R, n] (n <= LANES or a
    multiple of it): whole vregs side by side, no broadcast across lanes."""
    return x[:, :n] if n <= LANES else jnp.tile(x, (1, n // LANES))


# ---------------- forward ----------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, chunk, kv_len, sk):
    i = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    d = q_ref.shape[-1]

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute(mode):
        for rows, cols in _pieces(mode, block_q, block_k, chunk):
            q = q_ref[0, rows, :]                      # [R, D]
            k = k_ref[0, cols, :]                      # [C, D]
            v = v_ref[0, cols, :]
            nr, nc = rows.stop - rows.start, cols.stop
            s = jax.lax.dot_general(
                q, k, NT, preferred_element_type=jnp.float32) * scale
            if mode != "plain":
                s = jnp.where(
                    _valid(causal, kv_len, sk, (nr, nc),
                           i * block_q + rows.start, kb * block_k, 0),
                    s, NEG_INF)
            m_prev = m_scr[rows, :]                    # [R, LANES]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, nc))         # [R, C]
            corr = jnp.exp(m_prev - m_new)             # [R, LANES]
            l_scr[rows, :] = corr * l_scr[rows, :] + \
                p.sum(axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, NN,
                preferred_element_type=jnp.float32)    # [R, D]
            acc_scr[rows, :] = _lanes(corr, d) * acc_scr[rows, :] + pv
            m_scr[rows, :] = m_new

    _run_step(compute, causal=causal, padded=kv_len < sk, block_q=block_q,
              block_k=block_k, i=i, kb=kb, nk=nk)

    @pl.when(kb == nk - 1)
    def _final():
        l = jnp.maximum(l_scr[:], np.float32(1e-30))  # [BQ, LANES]
        o_ref[0] = (acc_scr[:] / _lanes(l, d)).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _last_k(causal, block_q, block_k):
    """Index map of a key-side block in the (b, i, kb) grids: under a causal
    mask, clamped to the last key block query block ``i`` sees."""
    if not causal:
        return lambda b, i, kb: (b, kb, 0)
    return lambda b, i, kb: (
        b, jnp.minimum(kb, (i * block_q + block_q - 1) // block_k), 0)


# jitted, so that every layer of a model shares one traced kernel: the step
# that calls them then traces and lowers each kernel once, not once a layer
# (the Mosaic lowering of a kernel is Python, runs before any compile cache
# is asked, and grows with the unrolled body)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _flash_fwd(q, k, v, scale, causal, blocks, interpret, kv_len):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q, block_k, chunk = blocks
    kernel = functools.partial(
        _fwd_kernel, scale=np.float32(scale), causal=causal, block_q=block_q,
        block_k=block_k, chunk=chunk, kv_len=kv_len, sk=sk)
    q_map = lambda b, i, kb: (b, i, 0)
    k_map = _last_k(causal, block_q, block_k)
    with _no_x64():
        o, lse = pl.pallas_call(
            kernel,
            grid=(bh, sq // block_q, sk // block_k),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_k, d), k_map),
                pl.BlockSpec((1, block_k, d), k_map),
            ],
            out_specs=(
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_q, LANES), q_map),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            out_shape=(jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                       jax.ShapeDtypeStruct((bh, sq, LANES), jnp.float32)),
            compiler_params=_compiler_params(
                "fwd", block_q, block_k, chunk, d, q.dtype),
            interpret=interpret,
            name="flash_attention_fwd",
        )(q, k, v)
    return o, lse


# ---------------- backward ----------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_k, chunk, kv_len,
                   sk):
    i = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute(mode):
        for rows, cols in _pieces(mode, block_q, block_k, chunk):
            q = q_ref[0, rows, :]                      # [R, D]
            do = do_ref[0, rows, :]
            k = k_ref[0, cols, :]                      # [C, D]
            v = v_ref[0, cols, :]
            nr, nc = rows.stop - rows.start, cols.stop
            s = jax.lax.dot_general(
                q, k, NT, preferred_element_type=jnp.float32) * scale
            if mode != "plain":
                s = jnp.where(
                    _valid(causal, kv_len, sk, (nr, nc),
                           i * block_q + rows.start, kb * block_k, 0),
                    s, NEG_INF)
            p = jnp.exp(s - _lanes(lse_ref[0, rows, :], nc))   # [R, C]
            dp = jax.lax.dot_general(
                do, v, NT, preferred_element_type=jnp.float32)
            # x scale once, in _final
            ds = p * (dp - _lanes(delta_ref[0, rows, :], nc))
            dq_scr[rows, :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, NN,
                preferred_element_type=jnp.float32)

    _run_step(compute, causal=causal, padded=kv_len < sk, block_q=block_q,
              block_k=block_k, i=i, kb=kb, nk=nk)

    @pl.when(kb == nk - 1)
    def _final():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_k, chunk, kv_len, sk):
    """On the transposed scores sT = k qT [C, R]: lse and delta come with
    the sequence on the lanes and broadcast down the sublanes, and both
    accumulating products (pT dO, dsT q) are plain a @ b."""
    kb = pl.program_id(1)
    ib = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(ib == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute(mode):
        for rows, cols in _pieces(mode, block_q, block_k, chunk):
            q = q_ref[0, rows, :]                      # [R, D]
            do = do_ref[0, rows, :]
            k = k_ref[0, cols, :]                      # [C, D]
            v = v_ref[0, cols, :]
            nr, nc = rows.stop - rows.start, cols.stop
            st = jax.lax.dot_general(
                k, q, NT, preferred_element_type=jnp.float32) * scale
            if mode != "plain":
                st = jnp.where(
                    _valid(causal, kv_len, sk, (nc, nr),
                           ib * block_q + rows.start, kb * block_k, 1),
                    st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, :, rows])     # [C, R]
            dv_scr[cols, :] += jax.lax.dot_general(
                pt.astype(do.dtype), do, NN,
                preferred_element_type=jnp.float32)    # [C, D]
            dpt = jax.lax.dot_general(
                v, do, NT, preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta_ref[0, :, rows])   # x scale in _final
            dk_scr[cols, :] += jax.lax.dot_general(
                dst.astype(q.dtype), q, NN,
                preferred_element_type=jnp.float32)    # [C, D]

    _run_step(compute, causal=causal, padded=kv_len < sk, block_q=block_q,
              block_k=block_k, i=ib, kb=kb, nk=sk // block_k)

    @pl.when(ib == nq - 1)
    def _final():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _flash_bwd(res, g, scale, causal, schedule, interpret, kv_len):
    q, k, v, o, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # [BH, SQ]
    static = dict(scale=np.float32(scale), causal=causal, kv_len=kv_len,
                  sk=sk)

    block_q, block_k, chunk = schedule.dq
    q_map = lambda b, i, kb: (b, i, 0)
    k_map = _last_k(causal, block_q, block_k)
    with _no_x64():
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, block_q=block_q,
                              block_k=block_k, chunk=chunk, **static),
            grid=(bh, sq // block_q, sk // block_k),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_k, d), k_map),
                pl.BlockSpec((1, block_k, d), k_map),
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_q, LANES), q_map),
                pl.BlockSpec((1, block_q, LANES), q_map),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), q_map),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=_compiler_params(
                "dq", block_q, block_k, chunk, d, q.dtype),
            interpret=interpret,
            name="flash_attention_bwd_dq",
        )(q, k, v, do,
          # the residual keeps one lane; the dq kernel reads row stats
          # replicated over the lanes
          jnp.broadcast_to(lse, (bh, sq, LANES)),
          jnp.broadcast_to(delta[..., None], (bh, sq, LANES)))

    block_q, block_k, chunk = schedule.dkv
    k_map = lambda b, kb, i: (b, kb, 0)
    if causal:
        # clamped to the first query block that sees key block ``kb``
        def first_q(i, kb):
            return jnp.maximum(i, (kb * block_k) // block_q)
    else:
        def first_q(i, kb):
            return i
    q_map = lambda b, kb, i: (b, first_q(i, kb), 0)
    row_map = lambda b, kb, i: (b, 0, first_q(i, kb))
    with _no_x64():
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, block_q=block_q,
                              block_k=block_k, chunk=chunk, **static),
            grid=(bh, sk // block_k, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_k, d), k_map),
                pl.BlockSpec((1, block_k, d), k_map),
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, 1, block_q), row_map),
                pl.BlockSpec((1, 1, block_q), row_map),
            ],
            out_specs=(pl.BlockSpec((1, block_k, d), k_map),
                       pl.BlockSpec((1, block_k, d), k_map)),
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)),
            compiler_params=_compiler_params(
                "dkv", block_q, block_k, chunk, d, q.dtype),
            interpret=interpret,
            name="flash_attention_bwd_dkv",
        )(q, k, v, do, lse.reshape(bh, 1, sq), delta.reshape(bh, 1, sq))
    return dq, dk, dv


# ---------------- public entry ----------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_bhsd(q, k, v, scale, causal, schedule, kv_len,
                          interpret):
    o, _ = _flash_fwd(q, k, v, scale, causal, schedule.fwd, interpret, kv_len)
    return o


def _fa_fwd(q, k, v, scale, causal, schedule, kv_len, interpret):
    o, lse = _flash_fwd(q, k, v, scale, causal, schedule.fwd, interpret,
                        kv_len)
    # only lane 0 is meaningful — keep one lane in the fwd->bwd residual
    # (128x less HBM held across the backward) and re-broadcast in _flash_bwd
    # named for a jax.checkpoint policy: one that saves "attn_out" keeps
    # this kernel out of the backward's second forward
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse[..., :1], "attn_out")
    return o, (q, k, v, o, lse)


def _fa_bwd(scale, causal, schedule, kv_len, interpret, res, g):
    return _flash_bwd(res, g, scale, causal, schedule, interpret, kv_len)


_flash_attention_bhsd.defvjp(_fa_fwd, _fa_bwd)


# the mesh axes a training batch is split over (fleet's topology names);
# 'pipe', 'sep' and 'model' never carry it
BATCH_AXES = ("data", "sharding")


def flash_batch_axes(mesh, batch, axes=BATCH_AXES):
    """The mesh axes the sharded flash path partitions a batch of
    ``batch`` sequences over: those of ``axes`` with a degree above 1,
    dropped from the right until the product of their degrees divides
    the batch (``()``: every rank keeps the whole batch)."""
    axes = tuple(a for a in axes if int(mesh.shape.get(a, 1)) > 1)
    while axes and batch % math.prod(int(mesh.shape[a]) for a in axes):
        axes = axes[:-1]
    return axes


def sharded_flash_attention(mesh, causal=True, scale=None,
                            batch_axes=BATCH_AXES, model_axis="model",
                            impl=None, block_q=None, block_k=None,
                            interpret=False):
    """Flash attention shard_map'd over the mesh (SNIPPETS [2]
    ``sharded_flash_attention`` shape): q/k/v ``[B, S, H, D]`` partitioned
    ``P(batch axes, None, model, None)`` -- heads over the model axis, the
    batch over EVERY axis of ``batch_axes`` that splits it
    (``flash_batch_axes``: 'data' and, under ZeRO, 'sharding', which is
    where ``shard_batch`` puts a hybrid step's sequences). Attention is
    local to a sequence and a head, so each rank runs the kernel on its
    own sequences and heads, and because the specs name every axis the
    operands are already split over, no collective appears around it:
    nothing is gathered on the way in, and the output leaves laid out as
    the row-parallel ``out_proj`` takes it. (An axis left out of the spec
    is an all-gather of q, k and v across it, forward and backward.)

    The axes are chosen when the returned function is traced, from the
    batch it is handed. ``impl(q, k, v)`` defaults to the Pallas kernel;
    pass the jnp reference chain for CPU parity tests (interpret mode
    measures the emulator, not the chip). A mesh with nothing to split
    over (every degree 1) returns the plain impl."""
    if impl is None:
        def impl(q, k, v):
            return flash_attention_bshd(q, k, v, causal=causal, scale=scale,
                                        block_q=block_q, block_k=block_k,
                                        interpret=interpret)
    if all(int(mesh.shape.get(a, 1)) <= 1 for a in (model_axis, *batch_axes)):
        return impl
    from jax.sharding import PartitionSpec as P

    @functools.lru_cache(maxsize=None)  # one entry a prefix of batch_axes
    def mapped(axes):
        spec = P(axes or None, None, model_axis, None)
        return jax.jit(jax.shard_map(impl, mesh=mesh,
                                     in_specs=(spec, spec, spec),
                                     out_specs=spec, check_vma=False))

    def attend(q, k, v):
        return mapped(flash_batch_axes(mesh, q.shape[0], batch_axes))(q, k, v)
    return attend


def flash_attention_bshd(q, k, v, causal=True, scale=None, block_q=None,
                         block_k=None, interpret=False):
    """Flash attention on [B, S, H, D] arrays (paddle layout). Returns the
    same layout. Pads S up to the schedule's blocks when needed; ``block_q``
    / ``block_k`` override the schedule for all three kernels."""
    b, s, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    schedule = block_schedule(s, sk, d, q.dtype)
    if block_q or block_k:
        schedule = _forced_schedule(s, sk, block_q or schedule.fwd[0],
                                    block_k or schedule.fwd[1])
    # the schedule is decided here, at trace time: say so once, behind the
    # trace buffer's one gate (off = one check, no call into tracing)
    tr = _trc._TR if _trc._loaded else _trc._load()
    if tr is not None:
        _record_schedule(tr, q.shape, sk, q.dtype, causal, schedule)

    def to_bhsd(x, pad):
        x = jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    o = _flash_attention_bhsd(
        to_bhsd(q, schedule.sq - s), to_bhsd(k, schedule.sk - sk),
        to_bhsd(v, schedule.sk - sk), scale, causal, schedule, sk, interpret)
    if schedule.sq != s:
        o = o[:, :s]
    return jnp.swapaxes(o.reshape(b, h, s, d), 1, 2)
