"""A decoder with multi-head latent attention and fine-grained experts
(the DeepSeek-V2/V3 block; Kimi-K2 runs it at its own sizes).

Every size is an argument of :class:`MLAMoEConfig`; nothing here is a
preset of one model. The block, with ``RMS`` an RMSNorm:

    h = x + MLA(RMS(x));   y = h + FFN(RMS(h))

* **MLA** — queries through a low-rank bottleneck (``q_lora_rank``; or
  straight from the hidden state where it is ``None``), optionally each
  head's output times a sigmoid gate (``attn_head_gate``), keys
  and values through ONE shared latent (``kv_lora_rank``) plus a decoupled
  rotary key common to all heads. What a token leaves behind is the row
  ``[c | k_rope]`` (``kv_lora_rank + qk_rope_head_dim`` values), after the
  norm and the rotation: :meth:`MLAMoEForCausalLM.cache_spec` declares it
  and the serving engine pages it. The ragged serving path attends in the
  absorbed form (the key up-projection folded into the query, the value
  up-projection applied after: 64 heads over one shared row, the kernel
  of ``ops/pallas/mla_ragged_attention.py``); the plain forward and
  ``generate`` expand keys and values from the latent.
* **FFN** — a gated-SiLU MLP in the first ``first_k_dense_replace``
  layers, then :class:`~paddle_tpu.incubate.moe.DroplessMoELayer` (shared
  expert, sigmoid router with a selection bias, renormalised top-k, no
  capacity). ``experts_held`` makes the model one expert-parallel rank's
  share: routing is over all experts, only the held ones compute.
* Rotary positions with YaRN (``rope_scaling``), pair ``(2i, 2i+1)``
  rotated by frequency ``i`` as DeepSeek-V3's reference code does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..incubate.moe import DroplessMoELayer
from .decoder_common import (GatedMLP, greedy_generate, positions,
                             rms as _rms, valid_tokens as _valid_tokens)

__all__ = ["MLAMoEConfig", "MLAMoEForCausalLM", "mla_moe_tiny",
           "yarn_inv_freq", "yarn_mscale"]


class MLAMoEConfig:
    def __init__(self, vocab_size=32000, hidden_size=1024, num_layers=4,
                 num_heads=8, q_lora_rank=256, kv_lora_rank=128,
                 qk_nope_head_dim=64, qk_rope_head_dim=32, v_head_dim=64,
                 intermediate_size=2816, moe_intermediate_size=512,
                 n_routed_experts=16, num_experts_per_tok=4,
                 n_shared_experts=1, first_k_dense_replace=1,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 experts_held=None, rms_norm_eps=1e-5, rope_theta=10000.0,
                 rope_scaling=None, max_seq_len=2048, dtype="float32",
                 cache_row_align=1, moe_backend=None, attn_head_gate=False):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        # None: queries straight from the hidden state, no bottleneck
        self.q_lora_rank = int(q_lora_rank) if q_lora_rank else None
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.n_shared_experts = int(n_shared_experts)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        # (lo, hi): the experts this copy of the model holds; None = all
        self.experts_held = tuple(experts_held) if experts_held else None
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        # YaRN: {"factor", "original_max_position_embeddings",
        # "beta_fast", "beta_slow", "mscale", "mscale_all_dim"} or None
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.max_seq_len = int(max_seq_len)
        self.dtype = dtype
        # the latent pool's rows are rounded up to this many values (128
        # on a TPU, where the page DMA wants whole lane tiles)
        self.cache_row_align = int(cache_row_align)
        self.moe_backend = moe_backend
        # each head's output times sigmoid(x W_gate), one gate a head
        self.attn_head_gate = bool(attn_head_gate)

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim


def mla_moe_tiny(**kw):
    """The size the CPU tests run: 1 dense + 2 expert layers, hidden 64,
    4 heads, latent 16 + 8, 16 experts top-4."""
    cfg = dict(vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
               q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, n_routed_experts=16,
               num_experts_per_tok=4, routed_scaling_factor=2.5,
               rope_theta=50000.0, max_seq_len=128,
               rope_scaling={"factor": 8, "beta_fast": 32, "beta_slow": 1,
                             "original_max_position_embeddings": 16,
                             "mscale": 1, "mscale_all_dim": 1})
    cfg.update(kw)
    return MLAMoEConfig(**cfg)


# ------------------------------------------------------------------ rope

def yarn_mscale(scale, mscale=1.0):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """Inverse frequencies of ``dim // 2`` rotary pairs, float32. With
    YaRN: pairs that turn more than ``beta_fast`` times over the original
    length keep their frequency, pairs that turn less than ``beta_slow``
    times are slowed by ``factor``, a linear ramp between."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if scaling:
        orig = scaling["original_max_position_embeddings"]

        def corr(turns):
            return dim * math.log(orig / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(corr(scaling["beta_fast"])), 0)
        high = min(math.ceil(corr(scaling["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / (high - low), 0, 1)
        keep = 1.0 - ramp
        inv = inv / scaling["factor"] * (1 - keep) + inv * keep
    return inv.astype(np.float32)


def _rope(x, pos, inv_freq, mscale):
    """Rotate pairs (2i, 2i+1) of ``x`` [..., S, (H,) D] by ``pos`` [.., S]
    (broadcast over a head axis if there is one)."""
    ang = pos.astype(jnp.float32)[..., None] * inv_freq      # [.., S, D/2]
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------- attention

class MLAttention(nn.Layer):
    def __init__(self, cfg: MLAMoEConfig):
        super().__init__()
        from ..nn import initializer as I
        self.cfg = cfg
        d, H = cfg.hidden_size, cfg.num_heads
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)

        def param(shape, fan_in):
            return self.create_parameter(
                shape, dtype=cfg.dtype,
                default_initializer=I.Normal(0.0, fan_in ** -0.5))

        def ones(n):
            return self.create_parameter(
                [n], dtype="float32", default_initializer=I.Constant(1.0))

        if cfg.q_lora_rank:
            self.q_a_proj = param([d, cfg.q_lora_rank], d)
            self.q_a_norm = ones(cfg.q_lora_rank)
            self.q_b_proj = param([cfg.q_lora_rank, H * (dn + dr)],
                                  cfg.q_lora_rank)
            self._q_weights = [self.q_a_proj, self.q_a_norm, self.q_b_proj]
        else:
            self.q_proj = param([d, H * (dn + dr)], d)
            self._q_weights = [self.q_proj]
        self.kv_a_proj = param([d, cfg.latent_width], d)
        self.kv_a_norm = ones(cfg.kv_lora_rank)
        # per head [k_nope | v] from the latent
        self.kv_b_proj = param([cfg.kv_lora_rank, H * (dn + dv)],
                               cfg.kv_lora_rank)
        self.gate_proj = param([d, H], d) \
            if getattr(cfg, "attn_head_gate", False) else None
        self.o_proj = param([H * dv, d], H * dv)
        sc = cfg.rope_scaling
        self._inv_freq = yarn_inv_freq(dr, cfg.rope_theta, sc)
        m_all = yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0)) \
            if sc else 1.0
        self._rope_mscale = np.float32(
            yarn_mscale(sc["factor"], sc.get("mscale", 1)) / m_all
            if sc else 1.0)
        self.scale = float((dn + dr) ** -0.5 * m_all * m_all)

    def _project(self, x, pos):
        """-> q_nope [B,S,H,dn], rotated q_rope [B,S,H,dr], and the row a
        token leaves behind, ``[c | k_rope]`` [B,S,latent_width]."""
        cfg = self.cfg
        H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        eps, inv, ms = cfg.rms_norm_eps, self._inv_freq, self._rope_mscale

        def fwd(xa, pa, *w):
            *wq, wkva, nkva = w
            B, S, _ = xa.shape
            if len(wq) == 3:        # through the bottleneck: a, norm, b
                cq = _rms(jnp.matmul(xa, wq[0]), wq[1], eps)
                q = jnp.matmul(cq, wq[2]).reshape(B, S, H, dn + dr)
            else:
                q = jnp.matmul(xa, wq[0]).reshape(B, S, H, dn + dr)
            kv = jnp.matmul(xa, wkva)
            c = _rms(kv[..., :cfg.kv_lora_rank], nkva, eps)
            k_rope = _rope(kv[..., cfg.kv_lora_rank:], pa, inv, ms)
            return (q[..., :dn], _rope(q[..., dn:], pa, inv, ms),
                    jnp.concatenate([c, k_rope], axis=-1))

        return apply("mla_project", fwd,
                     [x, pos, *self._q_weights, self.kv_a_proj,
                      self.kv_a_norm], nout=3)

    def _expanded(self, q_nope, q_rope, latent):
        """Plain attention with keys and values expanded from the latent
        rows ``latent`` [B, Sk, W] (the new tokens are its last S)."""
        cfg, scale = self.cfg, np.float32(self.scale)
        H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
        r = cfg.kv_lora_rank

        def fwd(qn, qr, lat, wkvb):
            B, S = qn.shape[:2]
            Sk = lat.shape[1]
            kv = jnp.matmul(lat[..., :r], wkvb).reshape(B, Sk, H, dn + dv)
            s = jnp.einsum("bqhd,bkhd->bhqk", qn, kv[..., :dn],
                           preferred_element_type=jnp.float32) \
                + jnp.einsum("bqhd,bkd->bhqk", qr, lat[..., r:],
                             preferred_element_type=jnp.float32)
            seen = jnp.arange(Sk)[None, :] <= (jnp.arange(S) + Sk - S)[:, None]
            p = jax.nn.softmax(jnp.where(seen, s * scale, -1e30), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(lat.dtype),
                           kv[..., dn:])
            return o.reshape(B, S, H * dv)

        return apply("mla_expanded_attention", fwd,
                     [q_nope, q_rope, latent, self.kv_b_proj])

    def _absorbed(self, q_nope, q_rope, cache):
        """The ragged round: attention of H heads over the paged latent
        rows, the key up-projection folded into the query."""
        cfg, scale = self.cfg, self.scale
        H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
        r = cfg.kv_lora_rank
        impl = cache.get("attn_impl")
        if impl is None:
            from ..ops.pallas.mla_ragged_attention import \
                mla_ragged_attention_reference as _ref
            impl = lambda q, p, *meta, **kw: _ref(q, p["latent"], *meta,
                                                  **kw)

        def fwd(qn, qr, wkvb, bt, rs, rl, kl, pool):
            w = wkvb.reshape(r, H, dn + dv)
            q_lat = jnp.einsum("thd,chd->thc", qn[0], w[..., :dn])
            q = jnp.concatenate([q_lat.astype(qr.dtype), qr[0]], axis=-1)
            o_lat = impl(q, {"latent": pool}, rs.astype(jnp.int32),
                         rl.astype(jnp.int32), kl.astype(jnp.int32),
                         bt.astype(jnp.int32), scale=scale, value_width=r)
            o = jnp.einsum("thc,chd->thd", o_lat, w[..., dn:])
            return o.reshape(1, -1, H * dv)

        return apply("mla_absorbed_attention", fwd,
                     [q_nope, q_rope, self.kv_b_proj, cache["block_tables"],
                      cache["row_starts"], cache["row_lens"],
                      cache["kv_lens"], cache["pools"]["latent"]])

    def forward(self, x, pos, cache=None):
        q_nope, q_rope, latent = self._project(x, pos)
        if cache is not None and cache.get("ragged"):
            from ..serving.kv_cache import pool_write_ragged
            cache["pools"]["latent"] = pool_write_ragged(
                cache["pools"]["latent"], latent, cache["block_tables"],
                cache["row_starts"], cache["row_lens"], cache["kv_lens"])
            out = self._absorbed(q_nope, q_rope, cache)
        else:
            if cache is not None:
                # the dense cache protocol: the rows this layer declared
                if cache.get("latent") is not None:
                    from .. import ops
                    latent = ops.concat([cache["latent"], latent], axis=1)
                cache["latent"] = latent
            out = self._expanded(q_nope, q_rope, latent)
        if self.gate_proj is not None:
            H = self.cfg.num_heads

            def gated(o, xa, wg):
                g = jax.nn.sigmoid(jnp.matmul(xa, wg).astype(jnp.float32))
                o = o.reshape(o.shape[:-1] + (H, -1)) * g[..., None]
                return o.reshape(xa.shape[:-1] + (-1,)).astype(xa.dtype)

            out = apply("mla_head_gate", gated, [out, x, self.gate_proj])
        return out.matmul(self.o_proj)


# ------------------------------------------------------------------ block

class MLAMoEBlock(nn.Layer):
    def __init__(self, cfg: MLAMoEConfig, index):
        super().__init__()
        self.input_norm = nn.RMSNorm(cfg.hidden_size,
                                     epsilon=cfg.rms_norm_eps)
        self.attn = MLAttention(cfg)
        self.post_norm = nn.RMSNorm(cfg.hidden_size,
                                    epsilon=cfg.rms_norm_eps)
        self.is_moe = index >= cfg.first_k_dense_replace
        if self.is_moe:
            self.mlp = DroplessMoELayer(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                n_shared_experts=cfg.n_shared_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                backend=cfg.moe_backend, dtype=cfg.dtype)
        else:
            self.mlp = GatedMLP(cfg, cfg.intermediate_size)

    def forward(self, x, pos, cache=None):
        x = x + self.attn(self.input_norm(x), pos, cache=cache)
        u = self.post_norm(x)
        if not self.is_moe:
            return x + self.mlp(u)
        ragged = cache is not None and cache.get("ragged")
        y, load = self.mlp(u, return_load=True,
                           token_mask=_valid_tokens(cache, x.shape[1])
                           if ragged else None)
        if ragged:
            # what this layer reports of the round beside the tokens:
            # [pairs, experts_idle, max_load] of the held experts
            cache["aux"] = {"moe.route": load._data}
        return x + y


class MLAMoEForCausalLM(nn.Layer):
    """Embedding, the blocks, a final RMSNorm and an untied head;
    ``forward(input_ids, caches, pos_offset)`` as ``GPTForCausalLM`` has,
    so ``generate`` and the serving engine call it alike."""

    def __init__(self, config: MLAMoEConfig):
        super().__init__()
        from ..nn import initializer as I
        self.config = cfg = config
        self.embed = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=I.Normal(0.0, 1.0))
        self.layers = nn.LayerList([MLAMoEBlock(cfg, i)
                                    for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [cfg.hidden_size, cfg.vocab_size], dtype=cfg.dtype,
            default_initializer=I.Normal(0.0, cfg.hidden_size ** -0.5))

    def cache_spec(self):
        """What each layer keeps for a cached token: ONE latent row
        ``[c | k_rope]`` shared by all heads; the query the attention
        takes is ``[q_lat | q_rope]`` a head."""
        from ..serving.kv_cache import LayerState
        cfg = self.config
        return [LayerState("mla_latent", {"latent": (cfg.latent_width,)},
                           self.embed._data.dtype,
                           (cfg.num_heads, cfg.latent_width),
                           row_align=cfg.cache_row_align)] * cfg.num_layers

    def forward(self, input_ids, caches=None, pos_offset=0):
        pos = positions(pos_offset, input_ids.shape[1])
        x = apply("embedding_lookup", lambda w, i: w[i],
                  [self.embed, input_ids])
        for i, block in enumerate(self.layers):
            x = block(x, pos, cache=None if caches is None else caches[i])
        return self.norm(x).matmul(self.lm_head)

    def generate(self, input_ids, max_new_tokens=32, eos_token_id=None):
        """Greedy decoding with the dense latent cache: the prompt in one
        forward, then a token at a time. -> ids [B, prompt + new]."""
        return greedy_generate(self, input_ids, max_new_tokens,
                               eos_token_id,
                               [{"latent": None} for _ in self.layers])
