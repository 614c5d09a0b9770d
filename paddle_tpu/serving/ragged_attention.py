# tpu-lint: hot-path
"""Ragged paged attention for serving — one kernel, one launch, no buckets.

The serving incarnation of ``ops/pallas/ragged_attention.py`` (Ragged
Paged Attention, arxiv 2604.15464; ROADMAP open item 2): the engine's
whole scheduler round — single-token decode rows, budgeted prefill
chunks, prompt tails behind prefix-cache hits — rides ONE flattened
``[total_tokens, H, Dh]`` launch described by per-row metadata
(``row_starts`` / ``row_lens`` / ``kv_lens`` / block tables). Only
``total_tokens`` is padded, up the small power-of-two schedule of
:func:`pad_total_tokens`, so the round's one callable has a few
shape-specializations.

Backend policy is the standing kernel rule:

* ``xla`` — :func:`~paddle_tpu.ops.pallas.ragged_attention.
  ragged_paged_attention_reference`: the gather/segment formulation XLA
  compiles anywhere (CPU-parity source of truth).
* ``pallas`` — the flat-token scalar-prefetch kernel. TPU-only.
* ``auto`` — :func:`ab_compare_ragged` times both at the engine's ragged
  shape through ``ops/pallas/_common.ab_gate`` (verdict cached under
  ``ragged_paged_attention``); Pallas serves only where it measurably
  wins and never off-TPU. :func:`resolve_backend` reads the choice:
  ``PADDLE_TPU_SERVING_ATTN`` then ``PADDLE_TPU_KERNELS`` then ``auto``.

Multi-chip serving shards along **KV heads** over the fleet mesh's
``model`` axis: query heads stay with their GQA group's KV head,
metadata replicates, no collective in the launch
(:func:`sharded_ragged_attention`).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas import _common as _gate
from ..ops.pallas._common import on_tpu
from ..ops.pallas.ragged_attention import (
    ragged_paged_attention as _pallas_ragged,
    ragged_paged_attention_reference as _xla_ragged,
)

__all__ = ["ragged_paged_attention", "sharded_ragged_attention",
           "ab_compare_ragged", "pad_total_tokens", "resolve_backend",
           "on_tpu"]

BACKENDS = ("xla", "pallas", "auto")

# smallest padded launch: decode-only rounds of small engines all share
# one program instead of one per active-row count
PAD_FLOOR = 8


def pad_total_tokens(n, floor=PAD_FLOOR):
    """The power-of-two token-pad schedule: the ONLY shape axis the
    ragged program specializes on. Distinct programs over a serving
    lifetime are bounded by ``log2(max_round_tokens / floor) + 1`` — the
    bucket grids' ``O(|batch| x |seq|)`` product is gone."""
    n = max(int(n), int(floor))
    p = 1
    while p < n:
        p *= 2
    return p


def ragged_paged_attention(q, k_pool, v_pool, row_starts, row_lens,
                           kv_lens, block_tables, backend="xla",
                           scale=None):
    """One ragged launch: ``q`` [T, H, Dh] flat tokens; pools
    [P, page, KVH, Dh]; per-row metadata as in the ops module. Returns
    [T, H, Dh]; pad tokens (past each row's ``row_lens``) come back
    zeroed and the caller discards them."""
    if backend == "pallas":
        return _pallas_ragged(q, k_pool, v_pool, row_starts, row_lens,
                              kv_lens, block_tables, scale=scale)
    return _xla_ragged(q, k_pool, v_pool, row_starts, row_lens, kv_lens,
                       block_tables, scale=scale)


def sharded_ragged_attention(mesh, axis_name="model", backend="xla",
                             scale=None):
    """Ragged attention sharded along KV heads over ``mesh[axis_name]``:
    each shard attends its query-head groups against its head
    slice of every page; row metadata and block tables replicate — no
    collective in the launch, the out_spec stitches heads back. Falls
    back to the unsharded fn when the axis degree is 1."""
    degree = int(mesh.shape.get(axis_name, 1))

    def _impl(q, kp, vp, rs, rl, kl, bt):
        return ragged_paged_attention(q, kp, vp, rs, rl, kl, bt,
                                      backend=backend, scale=scale)

    if degree <= 1:
        return _impl
    in_specs = (
        P(None, axis_name, None),         # q [T, H, Dh]
        P(None, None, axis_name, None),   # k_pool [P, page, KVH, Dh]
        P(None, None, axis_name, None),   # v_pool
        P(),                              # row_starts (replicated)
        P(),                              # row_lens
        P(),                              # kv_lens
        P(),                              # block_tables
    )
    out_specs = P(None, axis_name, None)
    # tpu-lint: ok[RC001] built once per engine at a fixed shape and invoked inside the engine's jitted round (nested jit inlines) — the round program is counted at its _note_program install site
    return jax.jit(jax.shard_map(_impl, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def resolve_backend(requested=None):
    """Normalize the backend choice: explicit arg wins, then the
    ``PADDLE_TPU_SERVING_ATTN`` env knob, then the global
    ``PADDLE_TPU_KERNELS`` gate knob, default ``auto``."""
    b = requested or os.environ.get("PADDLE_TPU_SERVING_ATTN") \
        or os.environ.get(_gate.KERNELS_ENV) or "auto"
    b = str(b).lower()
    if b not in BACKENDS:
        raise ValueError(
            f"unknown serving attention backend {b!r}; pick from "
            f"{BACKENDS}")
    return b


def ab_compare_ragged(q, k_pool, v_pool, row_starts, row_lens, kv_lens,
                      block_tables, scale=None, repeats=20):
    """Time the jitted XLA reference vs the Pallas ragged kernel at this
    exact launch shape through the generalized demotion gate — verdict
    recorded under ``ragged_paged_attention`` keyed by the leading-
    operand (q) sig, so bench rows and the engine share one cache.
    Off-TPU the Pallas leg is skipped (interpret mode measures the
    emulator, not the chip) and XLA wins by default.
    -> ``{"backend", "xla_ms", "pallas_ms", "reason"}``."""
    args = (q, k_pool, v_pool,
            jnp.asarray(row_starts, jnp.int32),
            jnp.asarray(row_lens, jnp.int32),
            jnp.asarray(kv_lens, jnp.int32),
            jnp.asarray(block_tables, jnp.int32))
    return _gate.ab_gate(
        "ragged_paged_attention",
        lambda *a: _xla_ragged(*a, scale=scale),
        lambda *a: _pallas_ragged(*a, scale=scale),
        args, repeats=repeats, sig=_gate.shape_sig(q))
