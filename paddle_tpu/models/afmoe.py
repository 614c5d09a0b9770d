"""A decoder with gated grouped-query attention over sliding windows and
full contexts mixed, sandwich norms and fine-grained experts (the ``afmoe``
block; Arcee's Trinity models run it at their own sizes).

Every size is an argument of :class:`AfmoeConfig`; nothing here is a preset
of one model. A layer, with ``N*`` RMS norms with a learned scale:

    a = N_input(h);  q, k, v, g = a Wq, a Wk, a Wv, a Wg
    q = N_q(q), k = N_k(k)            over each head's values
    o = softmax(q k^T / sqrt(Dh)) v   each KV head read by H / KVH heads
    h = h + N_post_attention((o * sigmoid(g)) Wo)
    h = h + N_post_mlp(f(N_pre_mlp(h)))

* **Attention** — ``layer_types`` says of each layer whether it is
  ``sliding_attention`` (rotary positions over the whole head, halves
  rotated against each other; token i sees token j where
  ``0 <= i - j < sliding_window``) or ``full_attention`` (NO positional
  encoding, every ``j <= i``). The output is gated element by element by
  ``sigmoid(g)`` before the output projection. What a token leaves behind
  is its keys and values of every KV head side by side (after the norm and
  the rotation): :meth:`AfmoeForCausalLM.cache_spec` declares them, with
  the layer's window, and the serving engine pages the window layers and
  the full layers as two groups (``ops/pallas/windowed_ragged_attention.py``
  reads both).
* **f** — a gated-SiLU MLP in the first ``num_dense_layers`` layers, then
  :class:`~paddle_tpu.incubate.moe.DroplessMoELayer` (shared expert,
  sigmoid router with a selection bias, renormalised and scaled top-k, no
  capacity). ``experts_held`` makes the model one expert-parallel rank's
  share: routing is over all experts, only the held ones compute.
* The embedding is scaled by ``sqrt(hidden_size)`` (``mup_enabled``); head
  and embedding are untied; no bias anywhere.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..incubate.moe import DroplessMoELayer
from .decoder_common import (GatedMLP, greedy_generate, positions, rms,
                             valid_tokens)

__all__ = ["AfmoeConfig", "AfmoeForCausalLM", "afmoe_tiny"]

SLIDING, FULL = "sliding_attention", "full_attention"


class AfmoeConfig:
    def __init__(self, vocab_size=32000, hidden_size=1024, num_layers=8,
                 num_heads=16, num_kv_heads=4, head_dim=64,
                 sliding_window=1024, global_attn_every_n_layers=4,
                 layer_types=None, num_dense_layers=2,
                 intermediate_size=4096, moe_intermediate_size=1024,
                 num_experts=32, num_experts_per_tok=4,
                 num_shared_experts=1, route_norm=True, route_scale=1.0,
                 experts_held=None, rms_norm_eps=1e-5, rope_theta=10000.0,
                 mup_enabled=True, max_seq_len=4096, dtype="float32",
                 moe_backend=None):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.sliding_window = int(sliding_window)
        # every n-th layer is full, the others slide; or each layer's kind
        # written out
        n = int(global_attn_every_n_layers)
        self.layer_types = list(layer_types) if layer_types is not None \
            else [FULL if (i + 1) % n == 0 else SLIDING
                  for i in range(self.num_layers)]
        if len(self.layer_types) != self.num_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types} does not give "
                             f"{SLIDING!r} or {FULL!r} for each of "
                             f"{self.num_layers} layers")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} KV heads")
        self.num_dense_layers = int(num_dense_layers)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.num_shared_experts = int(num_shared_experts)
        self.route_norm = bool(route_norm)
        self.route_scale = float(route_scale)
        # (lo, hi): the experts this copy of the model holds; None = all
        self.experts_held = tuple(experts_held) if experts_held else None
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.mup_enabled = bool(mup_enabled)
        self.max_seq_len = int(max_seq_len)
        self.dtype = dtype
        self.moe_backend = moe_backend

    def window(self, layer):
        """Tokens layer ``layer`` looks back, itself included; None = all."""
        return self.sliding_window if self.layer_types[layer] == SLIDING \
            else None


def afmoe_tiny(**kw):
    """The size the CPU tests run: 1 dense + 4 expert layers (sliding,
    sliding, sliding, sliding, full: a leading dense layer and one whole
    period), hidden 64, 6 query heads over 2 KV heads of 16, a window of
    12 tokens, 16 experts top-4 and a shared one."""
    cfg = dict(vocab_size=256, hidden_size=64, num_layers=5, num_heads=6,
               num_kv_heads=2, head_dim=16, sliding_window=12,
               layer_types=[SLIDING] * 4 + [FULL], num_dense_layers=1,
               intermediate_size=96, moe_intermediate_size=32,
               num_experts=16, num_experts_per_tok=4, route_scale=2.448,
               max_seq_len=128)
    cfg.update(kw)
    return AfmoeConfig(**cfg)


def _rope(x, pos, inv_freq):
    """Rotate ``x`` [.., S, heads, D] by ``pos`` [.., S]: the two halves
    of a head against each other, pair (i, i + D/2) by frequency i."""
    ang = pos.astype(jnp.float32)[..., None, None] * inv_freq   # [.,S,1,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class AfmoeAttention(nn.Layer):
    def __init__(self, cfg: AfmoeConfig, index):
        super().__init__()
        from ..nn import initializer as I
        self.cfg = cfg
        self.window = cfg.window(index)
        d, H, KVH, D = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                        cfg.head_dim)

        def param(shape, fan_in):
            return self.create_parameter(
                shape, dtype=cfg.dtype,
                default_initializer=I.Normal(0.0, fan_in ** -0.5))

        def ones(n):
            return self.create_parameter(
                [n], dtype="float32", default_initializer=I.Constant(1.0))

        # queries, keys, values and the output gate side by side: one
        # product of the normed input gives all four
        self.qkvg_proj = param([d, (2 * H + 2 * KVH) * D], d)
        self.q_norm, self.k_norm = ones(D), ones(D)
        self.o_proj = param([H * D, d], H * D)
        self._inv_freq = (1.0 / cfg.rope_theta ** (
            np.arange(0, D, 2, dtype=np.float64) / D)).astype(np.float32)
        self.scale = float(D ** -0.5)

    def _project(self, x, pos):
        """-> q [B,S,H,D] and k, v [B,S,KVH,D] (normed, and rotated in a
        sliding layer), and the gate [B,S,H*D]."""
        cfg, inv = self.cfg, self._inv_freq
        H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        eps, sliding = cfg.rms_norm_eps, self.window is not None

        def fwd(xa, pa, w, nq, nk):
            B, S, _ = xa.shape
            y = jnp.matmul(xa, w)
            q, k, v, g = jnp.split(
                y, [H * D, (H + KVH) * D, (H + 2 * KVH) * D], axis=-1)
            q = rms(q.reshape(B, S, H, D), nq, eps)
            k = rms(k.reshape(B, S, KVH, D), nk, eps)
            if sliding:
                q, k = _rope(q, pa, inv), _rope(k, pa, inv)
            return q, k, v.reshape(B, S, KVH, D), g

        return apply("afmoe_project", fwd,
                     [x, pos, self.qkvg_proj, self.q_norm, self.k_norm],
                     nout=4)

    def _plain(self, q, k, v):
        """Masked attention of the last ``S`` of ``Sk`` positions over all
        ``Sk`` (no cache, or the dense cache's rows)."""
        cfg, scale, window = self.cfg, np.float32(self.scale), self.window
        KVH = cfg.num_kv_heads

        def fwd(qa, ka, va):
            B, S, H, D = qa.shape
            Sk = ka.shape[1]
            s = jnp.einsum("bqkgd,bskd->bkgqs",
                           qa.reshape(B, S, KVH, H // KVH, D), ka,
                           preferred_element_type=jnp.float32) * scale
            i = (jnp.arange(S) + Sk - S)[:, None]
            j = jnp.arange(Sk)[None, :]
            seen = j <= i
            if window is not None:
                seen &= i - j < window
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(va.dtype), va)
            return o.reshape(B, S, H * D)

        return apply("afmoe_attention", fwd, [q, k, v])

    def _ragged(self, q, k, v, cache):
        """The serving round: this layer's keys and values into its pages,
        then ragged attention over the pages its window can see."""
        from ..serving.kv_cache import pool_write_ragged
        window = self.window
        meta = [cache[n] for n in ("block_tables", "row_starts", "row_lens",
                                   "kv_lens")]
        pools = cache["pools"]
        for name, rows in (("k", k), ("v", v)):
            flat = rows.reshape([1, rows.shape[1], -1])     # heads abreast
            pools[name] = pool_write_ragged(pools[name], flat, *meta)
        impl = cache.get("attn_impl")
        if impl is None:
            from ..ops.pallas.windowed_ragged_attention import \
                windowed_ragged_attention_reference as _ref
            impl = lambda qa, p, *m: _ref(qa, p["k"], p["v"], *m,
                                          window=window)

        def fwd(qa, bt, rs, rl, kl, kp, vp):
            out = impl(qa[0], {"k": kp, "v": vp}, rs.astype(jnp.int32),
                       rl.astype(jnp.int32), kl.astype(jnp.int32),
                       bt.astype(jnp.int32))
            return out.reshape(1, qa.shape[1], -1)

        return apply("afmoe_ragged_attention", fwd,
                     [q] + meta + [pools["k"], pools["v"]])

    def forward(self, x, pos, cache=None):
        q, k, v, gate = self._project(x, pos)
        if cache is not None and cache.get("ragged"):
            out = self._ragged(q, k, v, cache)
        else:
            if cache is not None:
                # the dense cache protocol: every row, whatever the window
                if cache.get("k") is not None:
                    from .. import ops
                    k = ops.concat([cache["k"], k], axis=1)
                    v = ops.concat([cache["v"], v], axis=1)
                cache["k"], cache["v"] = k, v
            out = self._plain(q, k, v)
        gated = apply("afmoe_output_gate",
                      lambda o, g: (o.astype(jnp.float32) * jax.nn.sigmoid(
                          g.astype(jnp.float32))).astype(o.dtype),
                      [out, gate])
        return gated.matmul(self.o_proj)


class AfmoeBlock(nn.Layer):
    def __init__(self, cfg: AfmoeConfig, index):
        super().__init__()

        def norm():
            return nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

        self.input_norm, self.post_attention_norm = norm(), norm()
        self.pre_mlp_norm, self.post_mlp_norm = norm(), norm()
        self.attn = AfmoeAttention(cfg, index)
        self.is_moe = index >= cfg.num_dense_layers
        if self.is_moe:
            self.mlp = DroplessMoELayer(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.num_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                n_shared_experts=cfg.num_shared_experts,
                routed_scaling_factor=cfg.route_scale,
                norm_topk_prob=cfg.route_norm,
                backend=cfg.moe_backend, dtype=cfg.dtype)
        else:
            self.mlp = GatedMLP(cfg, cfg.intermediate_size)

    def forward(self, x, pos, cache=None):
        x = x + self.post_attention_norm(
            self.attn(self.input_norm(x), pos, cache=cache))
        m = self.pre_mlp_norm(x)
        if not self.is_moe:
            return x + self.post_mlp_norm(self.mlp(m))
        ragged = cache is not None and cache.get("ragged")
        y, load = self.mlp(m, return_load=True,
                           token_mask=valid_tokens(cache, x.shape[1])
                           if ragged else None)
        if ragged:
            # what this layer reports of the round beside the tokens:
            # [pairs, experts_idle, max_load] of the held experts
            cache["aux"] = {"moe.route": load._data}
        return x + self.post_mlp_norm(y)


class AfmoeForCausalLM(nn.Layer):
    """Scaled embedding, the blocks, a final RMSNorm and an untied head;
    ``forward(input_ids, caches, pos_offset)`` as ``GPTForCausalLM`` has,
    so ``generate`` and the serving engine call it alike."""

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        from ..nn import initializer as I
        self.config = cfg = config
        self.embed = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=I.Normal(0.0, 1.0))
        self.layers = nn.LayerList([AfmoeBlock(cfg, i)
                                    for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [cfg.hidden_size, cfg.vocab_size], dtype=cfg.dtype,
            default_initializer=I.Normal(0.0, cfg.hidden_size ** -0.5))

    def cache_spec(self):
        """What each layer keeps for a cached token: its keys and values,
        the KV heads side by side in one row each, as far back as its
        window (None: all the way)."""
        from ..serving.kv_cache import LayerState
        cfg = self.config
        row = (cfg.num_kv_heads * cfg.head_dim,)
        return [LayerState("kv_windowed", {"k": row, "v": row},
                           self.embed._data.dtype,
                           (cfg.num_heads, cfg.head_dim),
                           window=cfg.window(i))
                for i in range(cfg.num_layers)]

    def forward(self, input_ids, caches=None, pos_offset=0):
        pos = positions(pos_offset, input_ids.shape[1])
        scale = math.sqrt(self.config.hidden_size) \
            if self.config.mup_enabled else 1.0
        x = apply("embedding_lookup",
                  lambda w, i: (w[i].astype(jnp.float32)
                                * np.float32(scale)).astype(w.dtype),
                  [self.embed, input_ids])
        for i, block in enumerate(self.layers):
            x = block(x, pos, cache=None if caches is None else caches[i])
        return self.norm(x).matmul(self.lm_head)

    def generate(self, input_ids, max_new_tokens=32, eos_token_id=None):
        """Greedy decoding with a dense cache of keys and values. -> ids
        [B, prompt + new]."""
        return greedy_generate(self, input_ids, max_new_tokens,
                               eos_token_id,
                               [{"k": None, "v": None} for _ in self.layers])
