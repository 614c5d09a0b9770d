"""Mixture-of-Experts with expert parallelism.

Reference: python/paddle/incubate/distributed/models/moe/moe_layer.py
(GShard-style all-to-all MoE) + gates (gate/{naive,gshard,switch}_gate.py)
and the dispatch kernels (assign_pos/limit_by_capacity).

TPU-native: the classic scatter/gather dispatch becomes the GShard einsum
formulation — dispatch/combine are one-hot matmuls over a capacity-limited
[tokens, experts, capacity] mask, and the expert FFNs are ONE batched matmul
over stacked weights [E, d, h] sharded on the expert axis. When the
dispatched tensor's expert dim is sharded, GSPMD emits exactly the all-to-all
the reference issues by hand — and it rides ICI.

Two layers, for two regimes:

* :class:`MoELayer` — Paddle's own API (gshard / switch gates, GELU
  experts, a capacity that drops overflow). The ``[T, E, C]`` mask is the
  right tool for a few experts (8-64) trained across an expert-parallel
  mesh axis.
* :class:`DroplessMoELayer` — hundreds of fine-grained experts, a sigmoid
  router with a selection bias, renormalised top-k weights, a shared
  expert, no capacity and no dropped token (DeepSeek-V3 / Kimi-K2 style;
  ``n_group`` / ``topk_group`` limit a token's experts to its best groups).
  Tokens bound for the experts this layer HOLDS (``experts_held``, a range
  of the whole set) are sorted by expert and run through one grouped
  matrix product (``ops/pallas/moe_grouped_matmul.py``); what absent
  experts would add is left out, for a layer that is one expert-parallel
  rank's share. The mask at E = 384 is neither that model nor computable.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.dispatch import apply
from ..core.tensor import Tensor
from .. import nn

__all__ = ["MoELayer", "TopKGate", "DroplessMoELayer", "sigmoid_topk_route",
           "dispatch_plan", "gated_silu"]


class TopKGate(nn.Layer):
    """top-1 (switch) / top-2 (gshard) softmax gate with load-balance loss.

    Reference: moe/gate/gshard_gate.py, switch_gate.py.
    """

    def __init__(self, d_model, num_experts, top_k=2, capacity_factor=1.25):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.weight = self.create_parameter([d_model, num_experts])

    def capacity(self, num_tokens):
        return int(math.ceil(num_tokens / self.num_experts
                             * self.capacity_factor * self.top_k))


class MoELayer(nn.Layer):
    """Reference: incubate/distributed/models/moe/moe_layer.py MoELayer.

    Expert FFN: x → gelu(x @ wi[e]) @ wo[e]. Experts stacked on dim 0 and
    sharded over the expert-parallel mesh axis (``moe_group``).
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, moe_group=None, gate=None, name=None):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.top_k = top_k
        self.gate = gate or TopKGate(d_model, num_experts, top_k,
                                     capacity_factor)
        self.wi = self.create_parameter([num_experts, d_model, d_hidden])
        self.wo = self.create_parameter([num_experts, d_hidden, d_model])
        self._group = moe_group
        if moe_group is not None:
            sharding = NamedSharding(moe_group.mesh,
                                     P(moe_group.axis, None, None))
            self.wi._data = jax.device_put(self.wi._data, sharding)
            self.wo._data = jax.device_put(self.wo._data, sharding)
        self.aux_loss = None

    def forward(self, x):
        """x: [..., d_model] → same shape. Sets self.aux_loss (load-balance,
        GShard eq.4) as a taped scalar for the training loss."""
        E = self.num_experts
        lead_shape = x.shape[:-1]
        n_tokens = int(np.prod(lead_shape))
        C = self.gate.capacity(n_tokens)
        top_k = self.top_k

        def fwd(xa, wg, wi, wo):
            xt = xa.reshape(n_tokens, self.d_model)
            logits = jnp.matmul(xt.astype(jnp.float32),
                                wg.astype(jnp.float32))      # [T, E]
            probs = jax.nn.softmax(logits, axis=-1)

            # top-k routing with capacity limiting (GShard)
            combine = jnp.zeros((n_tokens, E, C), jnp.float32)
            dispatch = jnp.zeros((n_tokens, E, C), bool)
            remaining = probs
            # position counters are built with cumsum per expert
            used = jnp.zeros((E,), jnp.int32)
            masks = []
            gates_k = []
            for _ in range(top_k):
                idx = jnp.argmax(remaining, axis=-1)          # [T]
                onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
                gates_k.append((remaining * onehot).sum(-1))  # [T]
                masks.append(onehot)
                remaining = remaining * (1 - onehot)
            if top_k > 1:
                # renormalize the k gate values (GShard)
                denom = sum(gates_k) + 1e-9
                gates_k = [g / denom for g in gates_k]
            # top_k == 1 keeps the raw top-1 probability as the combine
            # weight (reference switch_gate.py) so the gate gets gradient
            # through the expert output, not only the aux loss.

            pos_base = jnp.zeros((E,), jnp.float32)
            for onehot, gval in zip(masks, gates_k):
                # position of each token within its expert's capacity
                pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1) \
                    + pos_base[None, :]                        # [T, E]
                keep = (pos_in_expert < C) & (onehot > 0)
                pos = jnp.clip(pos_in_expert.astype(jnp.int32), 0, C - 1)
                cap_onehot = jax.nn.one_hot(pos, C,
                                            dtype=jnp.float32) * \
                    keep[..., None]                            # [T, E, C]
                combine = combine + cap_onehot * gval[:, None, None]
                dispatch = dispatch | (cap_onehot > 0)
                pos_base = pos_base + onehot.sum(axis=0)

            # dispatch tokens: [E, C, M]
            dispatched = jnp.einsum("tec,tm->ecm",
                                    dispatch.astype(xt.dtype), xt)
            h = jnp.einsum("ecm,emh->ech", dispatched, wi.astype(xt.dtype))
            h = jax.nn.gelu(h)
            eo = jnp.einsum("ech,ehm->ecm", h, wo.astype(xt.dtype))
            out = jnp.einsum("tec,ecm->tm", combine.astype(xt.dtype), eo)

            # load-balance aux loss (GShard): E * sum_e f_e * p_e
            me = probs.mean(axis=0)                           # [E]
            ce = masks[0].mean(axis=0)                        # top-1 fraction
            aux = (me * ce).sum() * E
            return out.reshape(xa.shape), aux

        out, aux = apply("moe", fwd, [x, self.gate.weight, self.wi, self.wo],
                         nout=2)
        self.aux_loss = aux
        return out


# ------------------------------------------------------ dropless routing

def _router_logits(u, weight):
    return jnp.matmul(u.astype(jnp.float32), weight.astype(jnp.float32),
                      precision="highest")


def sigmoid_topk_route(logits, bias, top_k, norm_topk_prob=True,
                       scaling=1.0, n_group=1, topk_group=1):
    """DeepSeek-V3's ``noaux_tc`` router, on raw arrays. ``logits`` [T, E]
    float32. The experts are the top-k of ``sigmoid(logits) + bias``; the
    bias steers the SELECTION only: the weights come from the unbiased
    scores, renormalised over the chosen k and scaled. With a group limit
    (``n_group`` > 1) the experts lie in ``n_group`` groups of equal
    size, a group scores the sum of its two largest ``sigmoid + bias``,
    and only the experts of the ``topk_group`` best groups can be chosen.
    -> ``(expert ids [T, k] int32, weights [T, k] float32)``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    pick = scores + bias.astype(jnp.float32)[None, :]
    if n_group > 1:
        T, E = pick.shape
        best2, _ = jax.lax.top_k(pick.reshape(T, n_group, E // n_group), 2)
        _, kept = jax.lax.top_k(best2.sum(-1), topk_group)
        keep = (kept[:, :, None] == jnp.arange(n_group)[None, None, :]) \
            .any(1)                                           # [T, groups]
        pick = jnp.where(jnp.repeat(keep, E // n_group, axis=1), pick,
                         -jnp.inf)
    _, idx = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * jnp.float32(scaling)


def dispatch_plan(idx, lo, n_held, tile_m):
    """Where each (token, expert) pair goes in the grouped product's row
    buffer. ``idx`` [T, k] expert ids over ALL experts; this layer holds
    ``[lo, lo + n_held)``. Pairs bound for a held expert are sorted by
    expert, each group padded to whole ``tile_m``-row tiles; no pair is
    dropped: the buffer is sized for every pair landing here.

    -> dict: ``dest`` [T*k] buffer row of each pair (``rows`` for a pair
    of an absent expert: a spare row that reads as zero), ``row_token``
    [rows] the token each buffer row copies (``T`` = a zero row),
    ``tile_expert`` [tiles], ``num_tiles`` [1], ``sizes`` [n_held] pairs a
    held expert, and the static ``rows``."""
    T, k = idx.shape
    n = T * k
    tiles = n // tile_m + n_held       # sum ceil(s_e / m) <= n // m + held
    rows = tiles * tile_m
    local = idx.reshape(-1) - lo
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    sizes = jnp.zeros(n_held + 1, jnp.int32).at[key].add(1)[:n_held]
    tiles_per = (sizes + tile_m - 1) // tile_m
    tile_end = jnp.cumsum(tiles_per)
    group_row0 = (tile_end - tiles_per) * tile_m
    group_first = jnp.cumsum(sizes) - sizes
    e = jnp.clip(sorted_key, 0, n_held - 1)
    j = jnp.arange(n, dtype=jnp.int32)
    dest_sorted = jnp.where(sorted_key < n_held,
                            group_row0[e] + j - group_first[e], rows)
    dest = jnp.zeros(n, jnp.int32).at[order].set(dest_sorted)
    token = (jnp.arange(n, dtype=jnp.int32) // k)
    row_token = jnp.full(rows + 1, T, jnp.int32).at[dest].set(token)[:rows]
    num_tiles = tile_end[-1:]
    t = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                    jnp.maximum(num_tiles[0] - 1, 0))
    tile_expert = jnp.clip(
        jnp.searchsorted(tile_end, t, side="right").astype(jnp.int32),
        0, n_held - 1)
    return {"dest": dest, "row_token": row_token,
            "tile_expert": tile_expert, "num_tiles": num_tiles,
            "sizes": sizes, "rows": rows}


def gated_silu(h, width):
    """``silu(gate) * up`` of ``h = [gate | up]``, in float32."""
    return (jax.nn.silu(h[..., :width].astype(jnp.float32))
            * h[..., width:].astype(jnp.float32)).astype(h.dtype)


class DroplessMoELayer(nn.Layer):
    """Shared expert + dropless top-k routing over ``num_experts`` gated-
    SiLU experts of width ``d_hidden``, of which this layer holds the
    range ``experts_held = (lo, hi)`` (default: all).

        y = Shared(u) + scaling * sum_{e in top_k(u), e held} g_e * Expert_e(u)

    Routing is over ALL experts (the router is whole on every rank); the
    part absent experts would add is left out and nothing stands in for
    them or their exchange. ``forward(x, return_load=True)`` also returns
    ``[pairs, experts_idle, max_load]`` (int32) of the held experts.

    ``backend``: ``"pallas"`` (the ``moe_grouped_matmul`` kernel),
    ``"pallas_interpret"`` (tests), ``"xla"`` (its twin); default Pallas
    on a TPU (``ops/pallas/_common.on_tpu``, asked when the layer is
    traced) and the twin elsewhere.
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k,
                 experts_held=None, n_shared_experts=1,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 backend=None, dtype=None, n_group=1, topk_group=1):
        super().__init__()
        from ..nn import initializer as I
        if num_experts % n_group or not 1 <= topk_group <= n_group:
            raise ValueError(f"{num_experts} experts in {n_group} groups, "
                             f"{topk_group} kept: no group limit")
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        lo, hi = experts_held if experts_held is not None \
            else (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"experts_held {experts_held} is no range of "
                             f"the {num_experts} experts")
        self.d_model, self.d_hidden = int(d_model), int(d_hidden)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.lo, self.n_held = int(lo), int(hi - lo)
        self.scaling = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.backend = backend
        d, h = self.d_model, self.d_hidden
        hs = h * int(n_shared_experts)
        self.shared_width = hs

        def param(shape, fan_in, **kw):
            return self.create_parameter(
                shape, dtype=dtype,
                default_initializer=I.Normal(0.0, fan_in ** -0.5), **kw)

        self.gate_weight = param([d, self.num_experts], d)
        # the selection bias (e_score_correction_bias): float32, a weight
        self.gate_bias = self.create_parameter(
            [self.num_experts], dtype="float32",
            default_initializer=I.Normal(0.0, 0.01))
        # gate and up projections side by side: one product gives both
        self.w13 = param([self.n_held, d, 2 * h], d)
        self.w2 = param([self.n_held, h, d], h)
        if hs:
            self.shared_w13 = param([d, 2 * hs], d)
            self.shared_w2 = param([hs, d], hs)

    def _gmm(self, x, w, plan, tile_m):
        from ..ops.pallas import _common, moe_grouped_matmul as _g
        backend = self.backend or ("pallas" if _common.on_tpu() else "xla")
        if backend == "xla":
            return _g.moe_grouped_matmul_reference(
                x, w, plan["tile_expert"], plan["num_tiles"], tile_m)
        return _g.moe_grouped_matmul(
            x, w, plan["tile_expert"], plan["num_tiles"], tile_m,
            interpret=backend == "pallas_interpret")

    def route(self, u):
        """Raw arrays: ``u`` [T, d] -> (ids [T, k], weights [T, k])."""
        return sigmoid_topk_route(
            _router_logits(u, self.gate_weight._data), self.gate_bias._data,
            self.top_k, self.norm_topk_prob, self.scaling, self.n_group,
            self.topk_group)

    def forward(self, x, return_load=False, token_mask=None):
        """``token_mask`` (bool, one a token): tokens that are padding;
        a False token is routed nowhere, so it costs no expert a copy of
        its weights and counts in no load."""
        d, h, hs = self.d_model, self.d_hidden, self.shared_width
        n_tokens = int(np.prod(x.shape[:-1]))
        n_pairs = n_tokens * self.top_k
        tile_m = 256 if n_pairs >= 4096 else 128 if n_pairs >= 1024 else 32

        masked = token_mask is not None

        def fwd(xa, wg, bg, w13, w2, *rest):
            shared = rest[masked:]
            u = xa.reshape(n_tokens, d)
            idx, wts = sigmoid_topk_route(
                _router_logits(u, wg), bg, self.top_k, self.norm_topk_prob,
                self.scaling, self.n_group, self.topk_group)
            if masked:
                idx = jnp.where(rest[0].reshape(n_tokens, 1), idx, -1)
            plan = dispatch_plan(idx, self.lo, self.n_held, tile_m)
            u_ext = jnp.concatenate([u, jnp.zeros((1, d), u.dtype)])
            xb = u_ext[plan["row_token"]]                      # [rows, d]
            act = gated_silu(self._gmm(xb, w13, plan, tile_m), h)
            yb = self._gmm(act, w2, plan, tile_m)              # [rows, d]
            dest = plan["dest"]
            live = dest < plan["rows"]
            y = jnp.where(live[:, None],
                          yb[jnp.minimum(dest, plan["rows"] - 1)], 0)
            y = (y.astype(jnp.float32).reshape(n_tokens, self.top_k, d)
                 * wts[..., None]).sum(1)
            if shared:
                s13, s2 = shared
                y = y + jnp.matmul(gated_silu(jnp.matmul(u, s13), hs),
                                   s2).astype(jnp.float32)
            sizes = plan["sizes"]
            load = jnp.stack([sizes.sum(), (sizes == 0).sum(),
                              sizes.max()]).astype(jnp.int32)
            return y.astype(xa.dtype).reshape(xa.shape), load

        ins = [x, self.gate_weight, self.gate_bias, self.w13, self.w2]
        if masked:
            ins.append(token_mask)
        if hs:
            ins += [self.shared_w13, self.shared_w2]
        out, load = apply("dropless_moe", fwd, ins, nout=2)
        return (out, load) if return_load else out
