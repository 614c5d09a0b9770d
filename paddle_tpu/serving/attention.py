# tpu-lint: hot-path
"""Attention backends by the KIND of state a layer declares.

The engine knows no model. A model's ``cache_spec()`` says what each layer
keeps for a token (:class:`~.kv_cache.LayerState`); the ``kind`` of that
declaration picks, here, the attention that can read such rows: how the
start-up gate times its two backends, and the function the round's
program hands the layer. It works on raw arrays and takes the layer's
pools as a dict ``name -> [P, page, *row]``.

* ``kv`` — keys and values by head: ``ragged_attention.py``.
* ``mla_latent`` — one shared latent row a token, read by every query
  head, values the first ``value_width`` entries of the same row:
  ``ops/pallas/mla_ragged_attention.py``.
* ``kv_windowed`` — keys and values with the KV heads side by side in one
  row, grouped-query, looking back ``spec.window`` tokens or all the way:
  ``ops/pallas/windowed_ragged_attention.py``.

* ``kda_state`` — no rows a token but one delta-rule state a REQUEST,
  held by slot and read and written once a launched row:
  ``ops/pallas/kda_ragged.py``.

Each class also says what a layer of its kind has to read in a round
(``rows_read``), under the name the ``decode_round`` span carries it;
``kv`` also says what its kernel's work items make of the round
(``work``).
"""
from __future__ import annotations

import jax
import numpy as np

from ..ops.pallas import _common as _gate
from ..ops.pallas.ragged_attention import item_plan, item_schedule
from .ragged_attention import (ab_compare_ragged, pad_total_tokens,
                               ragged_paged_attention,
                               sharded_ragged_attention)

__all__ = ["for_kind", "KVAttention", "LatentAttention",
           "WindowedAttention", "StateAttention"]


class KVAttention:
    """Keys and values by head, GQA-grouped; pools ``k`` and ``v``."""

    kind = "kv"

    def __init__(self, spec):
        self.spec = spec

    def rows_read(self, row_lens, kv_lens):
        """-> (the key of ``decode_round``'s row count, the rows a layer
        reads for these launched rows)."""
        return "kv_rows", int(np.sum(kv_lens))

    def work(self, row_lens, kv_lens, tokens, page_size):
        """What the Pallas kernel makes of these launched rows at token pad
        ``tokens``, a layer, from the plan alone (whatever backend runs):
        ``attn_items``, its work items that read a page, and
        ``attn_page_blocks``, the page blocks they fetch."""
        block_q, pages = item_schedule(page_size, self.spec.query[1], tokens)
        items, blocks = item_plan(row_lens, kv_lens, page_size, block_q,
                                  pages)
        return {"attn_items": items, "attn_page_blocks": blocks}

    def check_mesh(self, degree, axis):
        heads, kv_heads = self.spec.query[0], self.spec.rows["k"][0]
        if heads % degree or kv_heads % degree:
            raise ValueError(
                f"heads ({heads} query / {kv_heads} KV) not divisible by "
                f"mesh axis {axis}={degree} — GQA sharding splits both,"
                " keeping each query-head group with its KV head")

    def gate_ragged(self, pools, rows, tokens, page_size, max_pages,
                    max_seq_len):
        q = jax.random.normal(jax.random.PRNGKey(0),
                              (tokens,) + self.spec.query, self.spec.dtype)
        bt = np.zeros((rows, max_pages), np.int32)
        kl = np.full((rows,), min(page_size, max_seq_len), np.int32)
        return ab_compare_ragged(q, pools["k"], pools["v"],
                                 np.arange(rows, dtype=np.int32),
                                 np.ones(rows, np.int32), kl, bt)

    def impls(self, backend, mesh=None, mesh_axis="model"):
        if mesh is not None:
            ragged = sharded_ragged_attention(mesh, axis_name=mesh_axis,
                                              backend=backend)
        else:
            def ragged(q, kp, vp, rs, rl, kl, bt):
                return ragged_paged_attention(q, kp, vp, rs, rl, kl, bt,
                                              backend=backend)
        return lambda q, p, rs, rl, kl, bt: ragged(q, p["k"], p["v"], rs,
                                                   rl, kl, bt)


class LatentAttention:
    """One latent row a token shared by all query heads (MLA, absorbed
    form); pool ``latent``."""

    kind = "mla_latent"

    def __init__(self, spec):
        self.spec = spec

    def rows_read(self, row_lens, kv_lens):
        return "latent_rows", int(np.sum(kv_lens))

    def check_mesh(self, degree, axis):
        raise ValueError("a latent cache is one row for all heads: it "
                         f"cannot be split over mesh axis {axis}")

    def gate_ragged(self, pools, rows, tokens, page_size, max_pages,
                    max_seq_len):
        """Decode rows only, whatever the round's pad: the XLA twin
        gathers every row's whole block table for each token, which at
        a chunk's pad is tens of GiB; at ``rows`` tokens it is
        rows x max_seq_len x width."""
        from ..ops.pallas import mla_ragged_attention as _mla
        del tokens
        T = pad_total_tokens(rows)
        q = jax.random.normal(jax.random.PRNGKey(0),
                              (T,) + self.spec.query, self.spec.dtype)
        args = (q, pools["latent"], np.arange(rows, dtype=np.int32),
                np.ones(rows, np.int32),
                np.full((rows,), min(page_size, max_seq_len), np.int32),
                np.zeros((rows, max_pages), np.int32))
        return _gate.ab_gate(
            "mla_ragged_attention",
            _mla.mla_ragged_attention_reference, _mla.mla_ragged_attention,
            tuple(jax.numpy.asarray(a) for a in args),
            repeats=20, sig=_gate.shape_sig(q))

    def impls(self, backend, mesh=None, mesh_axis="model"):
        from ..ops.pallas import mla_ragged_attention as _mla
        fn = _mla.mla_ragged_attention if backend == "pallas" \
            else _mla.mla_ragged_attention_reference
        return lambda q, p, rs, rl, kl, bt, **kw: fn(
            q, p["latent"], rs, rl, kl, bt, **kw)


class WindowedAttention:
    """Keys and values with the KV heads side by side in one row (pools
    ``k`` and ``v``, ``[P, page, KVH * Dh]``), grouped-query, over the
    last ``spec.window`` tokens or over all of them."""

    kind = "kv_windowed"

    def __init__(self, spec):
        self.spec = spec
        self.window = spec.window

    def rows_read(self, row_lens, kv_lens):
        """A row of ``n`` tokens ending at context ``kv`` reads its last
        ``window + n - 1`` rows (what its first token sees, and the
        tokens after it), or all ``kv``. Host arrays, as the round's
        assembly holds them."""
        if self.window is None:
            return "kv_rows", int(np.sum(kv_lens))
        return "window_rows", int(np.minimum(
            kv_lens, row_lens + (self.window - 1)).sum())

    def check_mesh(self, degree, axis):
        raise ValueError("the windowed kernel reads every KV head of a "
                         f"page at once: no split over mesh axis {axis}")

    def gate_ragged(self, pools, rows, tokens, page_size, max_pages,
                    max_seq_len):
        """Decode rows only, each at a context of 16 pages (or the
        table's, if shorter), whatever the round's pad and the engine's
        table: the XLA twin gathers every row's whole block table for
        each token, rows x table x KVH x Dh values of keys and of values;
        at a 72-page table that is several GiB beside the weights."""
        from ..ops.pallas import windowed_ragged_attention as _win
        del tokens
        T = pad_total_tokens(rows)
        pages = min(max_pages, 16)
        q = jax.random.normal(jax.random.PRNGKey(0),
                              (T,) + self.spec.query, self.spec.dtype)
        args = (q, pools["k"], pools["v"], np.arange(rows, dtype=np.int32),
                np.ones(rows, np.int32),
                np.full((rows,), min(pages * page_size, max_seq_len),
                        np.int32),
                np.zeros((rows, pages), np.int32))
        return _gate.ab_gate(
            "windowed_ragged_attention",
            lambda *a: _win.windowed_ragged_attention_reference(
                *a, window=self.window),
            lambda *a: _win.windowed_ragged_attention(
                *a, window=self.window),
            tuple(jax.numpy.asarray(a) for a in args),
            repeats=20, sig=_gate.shape_sig(q))

    def impls(self, backend, mesh=None, mesh_axis="model"):
        from ..ops.pallas import windowed_ragged_attention as _win
        fn = _win.windowed_ragged_attention if backend == "pallas" \
            else _win.windowed_ragged_attention_reference
        window = self.window
        return lambda q, p, rs, rl, kl, bt, **kw: fn(
            q, p["k"], p["v"], rs, rl, kl, bt, window=window, **kw)


class StateAttention:
    """A delta-rule state a request (pool ``state``
    ``[slots + 1, H, Dk, Dv]`` float32, held by slot) beside the tail of
    its short convolution (pool ``conv``, which the layer itself reads and
    writes). The function it hands the layer is the recurrence alone:
    ``(q, k, v, alpha, beta, state, row_slots, row_starts, row_lens,
    kv_lens) -> (o, state)``."""

    kind = "kda_state"

    def __init__(self, spec):
        self.spec = spec

    def rows_read(self, row_lens, kv_lens):
        """One state a launched row, whatever its context."""
        return "state_rows", int(np.count_nonzero(row_lens))

    def chunk_tokens(self, row_lens):
        """The tokens of the rows longer than one token (prompt chunks):
        the part of a round's state work that grows with its tokens and
        not with its rows, whatever backend runs it."""
        return int(row_lens[row_lens > 1].sum())

    def check_mesh(self, degree, axis):
        raise ValueError("the state kernel takes whole head blocks of a "
                         f"slot: no split over mesh axis {axis}")

    def gate_ragged(self, pools, rows, tokens, page_size, max_pages,
                    max_seq_len):
        """Decode rows only (one token a slot), as the paged kinds gate:
        the twin runs the stream a token at a time."""
        from ..ops.pallas import kda_ragged as _kda
        del tokens, page_size, max_pages, max_seq_len
        T = pad_total_tokens(rows)
        H, D = self.spec.query
        key = jax.random.split(jax.random.PRNGKey(0), 5)
        q, k, v = (jax.random.normal(key[i], (T, H, D), "float32") * D ** -0.5
                   for i in range(3))
        alpha = jax.nn.sigmoid(jax.random.normal(key[3], (T, H, D)))
        beta = jax.nn.sigmoid(jax.random.normal(key[4], (T, H)))
        args = (q, k, v, alpha, beta, pools["state"],
                np.arange(1, rows + 1, dtype=np.int32),
                np.arange(rows, dtype=np.int32), np.ones(rows, np.int32),
                np.full((rows,), 2, np.int32))
        return _gate.ab_gate(
            "kda_ragged", _kda.kda_ragged_reference, _kda.kda_ragged,
            tuple(jax.numpy.asarray(a) for a in args),
            repeats=20, sig=_gate.shape_sig(q))

    def impls(self, backend, mesh=None, mesh_axis="model"):
        from ..ops.pallas import kda_ragged as _kda
        return _kda.kda_ragged if backend == "pallas" \
            else _kda.kda_ragged_reference


_KINDS = {c.kind: c for c in (KVAttention, LatentAttention,
                              WindowedAttention, StateAttention)}


def for_kind(spec):
    """The attention that reads ``spec``'s rows."""
    try:
        return _KINDS[spec.kind](spec)
    except KeyError:
        raise ValueError(
            f"no attention reads cached state of kind {spec.kind!r} "
            f"(known: {sorted(_KINDS)})") from None
