"""A decoder that mixes delta-rule linear attention with latent attention
over fine-grained experts (the Ling-3.0-flash block: Kimi Delta Attention,
arXiv:2510.26692, beside the DeepSeek-V2/V3 latent attention and
group-limited router).

Every size is an argument of :class:`KDAMLAMoEConfig`; nothing here is a
preset of one model. The block, with ``RMS`` an RMSNorm:

    h = x + Mix(RMS(x));   y = h + FFN(RMS(h))

* **Mix** is, by the layer's kind (``layer_kinds``):

  ``"kda"`` — :class:`KDAttention`. ``[q~ | k~ | v~] = a W_qkv``, each
  channel through a causal depthwise convolution of ``conv_taps`` taps over
  the request's own tokens and a SiLU; ``q`` and ``k`` L2-normalised a
  head; a decay a channel ``alpha = exp(lower * sigmoid(exp(A_log) *
  (a W_f + dt_bias)))`` and a step a head ``beta = sigmoid(a W_b)`` drive
  the delta rule on the head's state ``S`` (``[D, D]`` float32)

      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  and the output is ``(RMS_head(o) * sigmoid(a W_g)) W_o``. No positional
  encoding. What a request leaves behind is ``S`` and the last
  ``conv_taps - 1`` rows of ``[q~ | k~ | v~]``, whatever its length:
  :meth:`KDAMLAMoEForCausalLM.cache_spec` declares them ``per_request`` and
  the serving engine holds them by slot. The ragged serving path runs the
  recurrence through ``ops/pallas/kda_ragged.py``; the plain forward and
  ``generate`` scan it a token at a time.

  ``"mla"`` — :class:`~.mla_moe.MLAttention` with no query bottleneck, a
  sigmoid gate a head and plain rotary positions: the latent page cache
  and kernel of ``mla_moe.py``.

* **FFN** — a gated-SiLU MLP in the first ``num_dense_layers`` layers,
  then :class:`~paddle_tpu.incubate.moe.DroplessMoELayer` with a group
  limit on the router (``n_group`` groups, ``topk_group`` kept).
  ``experts_held`` makes the model one expert-parallel rank's share.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..incubate.moe import DroplessMoELayer
from ..nn import initializer as I
from .decoder_common import (GatedMLP, greedy_generate, positions,
                             rms as _rms, valid_tokens as _valid_tokens)
from .mla_moe import MLAttention

__all__ = ["KDAMLAMoEConfig", "KDAMLAMoEForCausalLM", "KDAttention",
           "kda_mla_moe_tiny", "short_conv", "kda_gates", "delta_rule_scan"]

F32 = jnp.float32


class KDAMLAMoEConfig:
    def __init__(self, vocab_size=32000, hidden_size=1024, num_layers=6,
                 num_heads=8, head_dim=64, layer_kinds=None,
                 layer_group_size=6, conv_taps=4, kda_lower_bound=-5.0,
                 kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=32,
                 v_head_dim=64, intermediate_size=2816,
                 moe_intermediate_size=512, n_routed_experts=16,
                 num_experts_per_tok=4, n_shared_experts=1, n_group=1,
                 topk_group=1, num_dense_layers=1,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 experts_held=None, rms_norm_eps=1e-6, rope_theta=10000.0,
                 max_seq_len=2048, dtype="float32", cache_row_align=1,
                 moe_backend=None):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)           # KDA's d_k = d_v
        # "kda" / "mla" a layer; by default the last of every
        # ``layer_group_size`` layers is latent attention
        self.layer_kinds = list(layer_kinds) if layer_kinds else [
            "mla" if (i + 1) % int(layer_group_size) == 0 else "kda"
            for i in range(self.num_layers)]
        if len(self.layer_kinds) != self.num_layers \
                or set(self.layer_kinds) - {"kda", "mla"}:
            raise ValueError(f"layer_kinds {self.layer_kinds} does not "
                             f"name {self.num_layers} kda / mla layers")
        self.conv_taps = int(conv_taps)
        self.kda_lower_bound = float(kda_lower_bound)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.n_shared_experts = int(n_shared_experts)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.num_dense_layers = int(num_dense_layers)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        # (lo, hi): the experts this copy of the model holds; None = all
        self.experts_held = tuple(experts_held) if experts_held else None
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.max_seq_len = int(max_seq_len)
        self.dtype = dtype
        self.cache_row_align = int(cache_row_align)
        self.moe_backend = moe_backend
        # what MLAttention reads beside the sizes above: queries straight
        # from the hidden state, plain rotary positions, a gate a head
        self.q_lora_rank = None
        self.rope_scaling = None
        self.attn_head_gate = True

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def conv_width(self):
        return 3 * self.num_heads * self.head_dim


def kda_mla_moe_tiny(**kw):
    """The size the CPU tests run: a dense KDA layer, an expert KDA layer
    and an expert MLA layer, hidden 64, 4 heads of 16, latent 16 + 8,
    16 experts in 4 groups (2 kept) top-4."""
    cfg = dict(vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
               head_dim=16, layer_kinds=["kda", "kda", "mla"],
               kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, n_routed_experts=16,
               num_experts_per_tok=4, n_group=4, topk_group=2,
               routed_scaling_factor=2.5, rope_theta=50000.0,
               max_seq_len=128)
    cfg.update(kw)
    return KDAMLAMoEConfig(**cfg)


# ------------------------------------------------------ raw-array pieces

def short_conv(x, history, weight):
    """Causal depthwise convolution and SiLU, float32. ``x`` [.., S, C],
    ``history`` [.., K-1, C] the K-1 inputs before ``x``'s first, ``weight``
    [K, C] (tap K-1 weighs a token's own input)."""
    K, S = weight.shape[0], x.shape[-2]
    w = weight.astype(F32)
    past = jnp.concatenate([history, x], axis=-2).astype(F32)
    return jax.nn.silu(sum(w[i] * past[..., i:i + S, :] for i in range(K)))


def kda_gates(conv, f, b, a_log, dt_bias, heads, dim, lower):
    """The recurrence's operands from a layer's projections, float32:
    ``conv`` [.., 3HD] the convolved ``[q | k | v]``, ``f`` [.., HD] the
    decay's projection, ``b`` [.., H] the step's.
    -> ``q, k, v, alpha`` [.., H, D], ``beta`` [.., H]."""
    lead = conv.shape[:-1]
    q, k, v = (conv[..., i * heads * dim:(i + 1) * heads * dim]
               .reshape(lead + (heads, dim)) for i in range(3))
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) \
        * np.float32(dim ** -0.5)
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    rate = jnp.exp(a_log.astype(F32))[:, None]
    g = np.float32(lower) * jax.nn.sigmoid(
        rate * (f.astype(F32) + dt_bias.astype(F32))
        .reshape(lead + (heads, dim)))
    return q, k, v, jnp.exp(g), jax.nn.sigmoid(b.astype(F32))


def delta_rule_scan(q, k, v, alpha, beta, state):
    """The recurrence a token at a time over ``[B, S, H, D]`` operands
    from ``state`` [B, H, D, D] -> (o [B, S, H, D], the last state)."""
    def token(S, t):
        qt, kt, vt, at, bt = t
        A = S * at[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, A,
                                             precision="highest"))
        S = A + kt[..., None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", qt, S, precision="highest")

    state, o = jax.lax.scan(
        token, state, tuple(jnp.moveaxis(a, 1, 0)
                            for a in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1), state


# ------------------------------------------------------------- attention

def _softplus_inv(y):
    return y + jnp.log(-jnp.expm1(-y))


class _Drawn(I.Uniform):
    """A uniform draw in ``[low, high)`` passed through ``fn``."""

    def __init__(self, low, high, fn):
        super().__init__(low, high)
        self.fn = fn

    def _generate(self, shape, dtype):
        return self.fn(super()._generate(shape, F32)).astype(dtype)


class KDAttention(nn.Layer):
    def __init__(self, cfg: KDAMLAMoEConfig):
        super().__init__()
        self.cfg = cfg
        d, H, D = cfg.hidden_size, cfg.num_heads, cfg.head_dim

        def param(shape, fan_in):
            return self.create_parameter(
                shape, dtype=cfg.dtype,
                default_initializer=I.Normal(0.0, fan_in ** -0.5))

        def f32(shape, init):
            return self.create_parameter(shape, dtype="float32",
                                         default_initializer=init)

        self.qkv_proj = param([d, cfg.conv_width], d)
        self.conv_weight = param([cfg.conv_taps, cfg.conv_width],
                                 cfg.conv_taps)
        self.f_proj = param([d, H * D], d)
        # the gate's two seeded vectors as Mamba-2's reference layer draws
        # them (arXiv:2405.21060; Gated DeltaNet, arXiv:2412.06464, took
        # the draw over): a rate a head uniform in [1, 16], a step a
        # channel log-uniform in [0.001, 0.1] (floor 1e-4) kept as its
        # inverse softplus. Under the safe gate three channels in five
        # then hardly decay (-g under 0.001 a token): the state forgets
        # by the delta rule's own overwriting
        self.dt_bias = f32([H * D], _Drawn(
            math.log(1e-3), math.log(1e-1),
            lambda u: _softplus_inv(jnp.maximum(jnp.exp(u), 1e-4))))
        self.a_log = f32([H], _Drawn(1.0, 16.0, jnp.log))
        self.b_proj = param([d, H], d)
        self.g_proj = param([d, H * D], d)
        self.o_norm = f32([D], I.Constant(1.0))
        self.o_proj = param([H * D, d], H * D)

    def _weights(self):
        return [self.qkv_proj, self.conv_weight, self.f_proj, self.dt_bias,
                self.a_log, self.b_proj, self.g_proj, self.o_norm]

    def _out(self, o, a, wg, no):
        """``RMS_head(o) * sigmoid(a W_g)``, in the model's dtype."""
        cfg = self.cfg
        o = _rms(o, no, cfg.rms_norm_eps).reshape(a.shape[:-1] + (-1,))
        return (o * jax.nn.sigmoid(jnp.matmul(a, wg).astype(F32))) \
            .astype(a.dtype)

    def _dense(self, x, cache):
        """[B, S, d] with the dense cache protocol: ``cache["state"]``
        [B, H, D, D] and ``cache["conv"]`` [B, K-1, C], or None."""
        cfg = self.cfg
        H, D, K = cfg.num_heads, cfg.head_dim, cfg.conv_taps
        have = cache is not None and cache.get("state") is not None

        def fwd(a, wqkv, wc, wf, dtb, alog, wb, wg, no, *kept):
            B = a.shape[0]
            pre = jnp.matmul(a, wqkv)
            state, hist = kept if kept else (
                jnp.zeros((B, H, D, D), F32),
                jnp.zeros((B, K - 1, pre.shape[-1]), pre.dtype))
            q, k, v, alpha, beta = kda_gates(
                short_conv(pre, hist, wc), jnp.matmul(a, wf),
                jnp.matmul(a, wb), alog, dtb, H, D, cfg.kda_lower_bound)
            o, state = delta_rule_scan(q, k, v, alpha, beta, state)
            tail = jnp.concatenate([hist, pre], axis=1)[:, -(K - 1):]
            return self._out(o, a, wg, no), state, tail

        out, state, tail = apply(
            "kda_dense", fwd, [x, *self._weights()]
            + ([cache["state"], cache["conv"]] if have else []), nout=3)
        if cache is not None:
            cache["state"], cache["conv"] = state, tail
        return out

    def _ragged(self, x, cache):
        """The serving round's flat stream [1, T, d]: the convolution's
        history of a row's first tokens and each row's state come from the
        request's slot, and go back there."""
        cfg = self.cfg
        H, D, K = cfg.num_heads, cfg.head_dim, cfg.conv_taps
        impl = cache.get("attn_impl")
        if impl is None:
            from ..ops.pallas.kda_ragged import kda_ragged_reference as impl

        def fwd(xa, wqkv, wc, wf, dtb, alog, wb, wg, no, rs, rl, kl, slots,
                conv_pool, state_pool):
            from ..ops.pallas.ragged_attention import ragged_row_index
            a = xa[0]
            T = a.shape[0]
            rs, rl, kl, slots = (m.astype(jnp.int32)
                                 for m in (rs, rl, kl, slots))
            pre = jnp.matmul(a, wqkv)                          # [T, C]
            rid, _, valid = ragged_row_index(rs, rl, kl, T)
            j = jnp.arange(T, dtype=jnp.int32) - rs[rid]  # place in the row
            # a row at the start of its context has no history
            hist = jnp.where((kl == rl)[:, None, None], 0,
                             conv_pool[slots])              # [R, K-1, C]
            w = wc.astype(F32)
            acc = w[K - 1] * pre.astype(F32)
            for i in range(1, K):
                # the input i tokens back: the stream's, or the slot's tail
                back = jnp.where(
                    (j >= i)[:, None], jnp.roll(pre, i, axis=0),
                    hist[rid, jnp.clip(K - 1 + j - i, 0, K - 2)])
                acc = acc + w[K - 1 - i] * back.astype(F32)
            conv = jnp.where(valid[:, None], jax.nn.silu(acc), 0.0)
            # what the row leaves: the last K-1 of [history | its tokens]
            m = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
            at = rl[:, None] + m                                # [R, K-1]
            tail = jnp.where(
                (at >= K - 1)[..., None],
                pre[jnp.clip(rs[:, None] + at - (K - 1), 0, T - 1)],
                hist[jnp.arange(rs.shape[0])[:, None],
                     jnp.clip(at, 0, K - 2)])
            conv_pool = conv_pool.at[slots].set(tail.astype(conv_pool.dtype))
            q, k, v, alpha, beta = kda_gates(
                conv, jnp.matmul(a, wf), jnp.matmul(a, wb), alog, dtb, H, D,
                cfg.kda_lower_bound)
            o, state_pool = impl(q, k, v, alpha, beta, state_pool, slots,
                                 rs, rl, kl)
            return self._out(o, a, wg, no)[None], conv_pool, state_pool

        pools = cache["pools"]
        out, pools["conv"], pools["state"] = apply(
            "kda_ragged_mix", fwd,
            [x, *self._weights(), cache["row_starts"], cache["row_lens"],
             cache["kv_lens"], cache["row_slots"], pools["conv"],
             pools["state"]], nout=3)
        return out

    def forward(self, x, cache=None):
        if cache is not None and cache.get("ragged"):
            out = self._ragged(x, cache)
        else:
            out = self._dense(x, cache)
        return out.matmul(self.o_proj)


# ------------------------------------------------------------------ block

class KDAMLAMoEBlock(nn.Layer):
    def __init__(self, cfg: KDAMLAMoEConfig, index):
        super().__init__()
        self.kind = cfg.layer_kinds[index]
        self.input_norm = nn.RMSNorm(cfg.hidden_size,
                                     epsilon=cfg.rms_norm_eps)
        self.attn = KDAttention(cfg) if self.kind == "kda" \
            else MLAttention(cfg)
        self.post_norm = nn.RMSNorm(cfg.hidden_size,
                                    epsilon=cfg.rms_norm_eps)
        self.is_moe = index >= cfg.num_dense_layers
        if self.is_moe:
            self.mlp = DroplessMoELayer(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                n_shared_experts=cfg.n_shared_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                backend=cfg.moe_backend, dtype=cfg.dtype,
                n_group=cfg.n_group, topk_group=cfg.topk_group)
        else:
            self.mlp = GatedMLP(cfg, cfg.intermediate_size)

    def forward(self, x, pos, cache=None):
        a = self.input_norm(x)
        x = x + (self.attn(a, cache=cache) if self.kind == "kda"
                 else self.attn(a, pos, cache=cache))
        u = self.post_norm(x)
        if not self.is_moe:
            return x + self.mlp(u)
        ragged = cache is not None and cache.get("ragged")
        y, load = self.mlp(u, return_load=True,
                           token_mask=_valid_tokens(cache, x.shape[1])
                           if ragged else None)
        if ragged:
            # [pairs, experts_idle, max_load] of the held experts
            cache["aux"] = {"moe.route": load._data}
        return x + y


class KDAMLAMoEForCausalLM(nn.Layer):
    """Embedding, the blocks, a final RMSNorm and an untied head;
    ``forward(input_ids, caches, pos_offset)`` as ``GPTForCausalLM`` has,
    so ``generate`` and the serving engine call it alike."""

    def __init__(self, config: KDAMLAMoEConfig):
        super().__init__()
        self.config = cfg = config
        self.embed = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=I.Normal(0.0, 1.0))
        self.layers = nn.LayerList([KDAMLAMoEBlock(cfg, i)
                                    for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [cfg.hidden_size, cfg.vocab_size], dtype=cfg.dtype,
            default_initializer=I.Normal(0.0, cfg.hidden_size ** -0.5))

    def cache_spec(self):
        """A ``kda`` layer keeps, a REQUEST, the state of each head
        (float32) and the last ``conv_taps - 1`` inputs of its
        convolution; an ``mla`` layer, a token, one latent row."""
        from ..serving.kv_cache import LayerState
        cfg = self.config
        dtype = self.embed._data.dtype
        H, D = cfg.num_heads, cfg.head_dim
        kinds = {
            "kda": LayerState(
                "kda_state", {"state": (H, D, D),
                              "conv": (cfg.conv_taps - 1, cfg.conv_width)},
                dtype, (H, D), per_request=True,
                row_dtypes={"state": jnp.dtype("float32")}),
            "mla": LayerState("mla_latent", {"latent": (cfg.latent_width,)},
                              dtype, (H, cfg.latent_width),
                              row_align=cfg.cache_row_align)}
        return [kinds[k] for k in cfg.layer_kinds]

    def forward(self, input_ids, caches=None, pos_offset=0):
        pos = positions(pos_offset, input_ids.shape[1])
        x = apply("embedding_lookup", lambda w, i: w[i],
                  [self.embed, input_ids])
        for i, block in enumerate(self.layers):
            x = block(x, pos, cache=None if caches is None else caches[i])
        return self.norm(x).matmul(self.lm_head)

    def generate(self, input_ids, max_new_tokens=32, eos_token_id=None):
        """Greedy decoding with the dense caches (a state and a tail a
        ``kda`` layer, the latent rows an ``mla`` layer): the prompt in
        one forward, then a token at a time. -> ids [B, prompt + new]."""
        return greedy_generate(
            self, input_ids, max_new_tokens, eos_token_id,
            [{"state": None, "conv": None} if k == "kda"
             else {"latent": None} for k in self.config.layer_kinds])
