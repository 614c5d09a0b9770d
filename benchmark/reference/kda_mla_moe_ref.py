"""Plain reference for the ``kda_mla_moe`` family (Ling-3.0-flash's block
at any sizes): straightforward ``jax.numpy``, float32 with
``jax.default_matmul_precision("highest")``, written from the equations of
Kimi Linear (arXiv:2510.26692, sections 2-3: Kimi Delta Attention), of
the DeepSeek-V2 / V3 reports (arXiv:2405.04434 section 2.1,
arXiv:2412.19437 sections 2.1.1-2.1.2: latent attention and the
group-limited router) and the source's ``config.json`` keys.

No kernel, no cache, no batching, no absorbed products, no chunked form of
the recurrence, and nothing imported from the program under test: the
recurrence runs a token at a time over the whole sequence, keys and values
of the latent layers are expanded for the whole sequence. Weights arrive in
whatever float type the program holds and are upcast one layer (one
expert) at a time; experts, projections and attention go in blocks of
tokens (memory only: they act on each token or query alone).

    x = E[ids]
    per layer:  h = x + Mix(RMS(x));  y = h + FFN(RMS(h))
    logits = RMS(y) @ W_head

Mix is KDA or MLA by the layer's ``kind``.

KDA (H heads of d_k = d_v = D), on the normed input a:
    [q~ | k~ | v~] = a W_qkv;  each channel through a causal depthwise
    convolution of K taps over the sequence (zeros before its first
    token), then SiLU;
    q = q~ / |q~| * D^-0.5,  k = k~ / |k~|   (L2 over a head's D, the
    norm's square plus 1e-6 under the root);
    g = lower * sigmoid(exp(A_log_h) * (a W_f + dt_bias)),  alpha = exp(g);
    beta = sigmoid(a W_b);
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,
    S_0 = 0,  o_t = S_t^T q_t;
    out = (RMS_head(o_t) * sigmoid(a W_g)) W_o.

MLA (H heads, no query bottleneck):  [q_nope | q_rope] = a W_q  per head;
[c_kv | k_r] = a W_kva;  c = RMS(c_kv);  [k_nope | v] = c W_kvb  per head;
score_ij = (q_nope_i . k_nope_j + RoPE(q_rope_i) . RoPE(k_r_j)) * scale,
causal softmax, each head's output times sigmoid(a W_gate) (one gate a
head), then W_o. RoPE: pair (2i, 2i+1) turns by  pos * theta^(-2i/d_r).

FFN: dense layers  W_2 (silu(u W_g) * (u W_u));  expert layers
Shared(u) + scaling * sum_{e chosen} w_e Expert_e(u)  with
s = sigmoid(u W_r); selection on s + b: the experts in ``n_group`` groups,
a group's score the sum of its two largest s + b, the ``topk_group`` best
groups kept, the k largest s + b among their experts; w_e = s_e / sum of
the chosen s (weights from the UNBIASED scores). Only the experts in
``experts_held`` contribute: the reference leaves out what the program
leaves out (one expert-parallel rank's share).

The state is float32. ``state_dtype`` (an argument of ``hidden`` and
``logits_at``) is for a probe that reads what a state kept in the nearest
precision below gives: the state is then rounded after every token
(``benchmark/tools/probe_state.py``).

Weights arrive as::

    {"cfg": {num_heads, head_dim (KDA's D), conv_taps, kda_lower_bound,
             kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
             num_experts_per_tok, n_group, topk_group,
             routed_scaling_factor, norm_topk_prob, experts_held (lo, hi),
             rms_norm_eps, rope_theta},
     "embed": [V, d], "norm": [d], "lm_head": [d, V],
     "layers": [{"kind": "kda" | "mla", "input_norm": [d],
                 "post_norm": [d], "ffn": ...,
          kda:   "qkv": [d, 3HD], "conv_w": [K, 3HD] (tap K-1 is the
                 token's own), "f": [d, HD], "dt_bias": [HD],
                 "a_log": [H], "b": [d, H], "g": [d, HD], "o_norm": [D],
                 "o": [HD, d]
          mla:   "q": [d, H*(dn+dr)], "kv_a": [d, rkv+dr],
                 "kv_a_norm": [rkv], "kv_b": [rkv, H*(dn+dv)],
                 "gate": [d, H], "o": [H*dv, d]}]}

with matrices stored ``[in, out]``, ``ffn`` as ``mla_moe_ref`` takes it
(``w13`` = ``[gate | up]``), ``q`` columns by head ``[nope | rope]`` and
``kv_b`` columns by head ``[k_nope | v]``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
TOKEN_CHUNK = 2048     # tokens a slice of the MLPs and projections
QUERY_BLOCK = 256      # queries a block of attention (memory only)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(F32)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _gated(u, w13, w2):
    h = u @ w13.astype(F32)
    f = h.shape[-1] // 2
    return (_silu(h[..., :f]) * h[..., f:]) @ w2.astype(F32)


def _by_chunks(fn, u, chunk=TOKEN_CHUNK):
    """Apply a per-token function to [S, d] in slices of ``chunk``."""
    s = u.shape[0]
    if s <= chunk:
        return fn(u)
    pad = -s % chunk
    v = jnp.pad(u, ((0, pad), (0, 0))).reshape(-1, chunk, u.shape[1])
    out = jax.lax.map(fn, v)
    return out.reshape(-1, out.shape[-1])[:s]


def _rope(x, pos, theta):
    """x [S, ..., D], pos [S]: pair (2i, 2i+1) turns by pos * f_i."""
    shape = x.shape
    d = shape[-1]
    f = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * f[None, :]
    x = x.reshape(shape[:-1] + (d // 2, 2))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x.ndim - 1:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1) \
        .reshape(shape)


def _cfg_key(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in cfg.items()))


# -------------------------------------------------------------------- KDA

@functools.partial(jax.jit, static_argnames=("key", "state_dtype"))
def _kda(x, lw, key, state_dtype):
    cfg = dict(key)
    with jax.default_matmul_precision("highest"):
        H, D, K = cfg["num_heads"], cfg["head_dim"], cfg["conv_taps"]
        eps, lower = cfg["rms_norm_eps"], cfg["kda_lower_bound"]
        S = x.shape[0]

        def project(t):
            a = _rms(t, lw["input_norm"], eps)
            return jnp.concatenate(
                [a @ lw["qkv"].astype(F32), a @ lw["f"].astype(F32),
                 a @ lw["b"].astype(F32), a @ lw["g"].astype(F32)], -1)

        p = _by_chunks(project, x)
        cut = np.cumsum([0, 3 * H * D, H * D, H, H * D])
        qkv, f, b, gate = (p[:, i:j] for i, j in zip(cut, cut[1:]))
        # the convolution: tap K-1 weighs the token itself, tap K-1-i the
        # token i before it; zeros before the sequence's first token
        w = lw["conv_w"].astype(F32)
        past = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
        qkv = _silu(sum(w[i][None, :] * past[i:i + S] for i in range(K)))
        q, k, v = (qkv[:, i * H * D:(i + 1) * H * D].reshape(S, H, D)
                   for i in range(3))
        q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) \
            * np.float32(D ** -0.5)
        k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
        rate = jnp.exp(lw["a_log"].astype(F32))[None, :, None]
        g = lower * _sigmoid(
            rate * (f + lw["dt_bias"].astype(F32)[None, :]).reshape(S, H, D))
        alpha, beta = jnp.exp(g), _sigmoid(b)                # [S,H,D] [S,H]

        def token(state, t):
            qt, kt, vt, at, bt = t
            decayed = state.astype(F32) * at[:, :, None]     # [H, dk, dv]
            seen = jnp.einsum("hk,hkv->hv", kt, decayed)
            state = decayed + bt[:, None, None] * kt[:, :, None] \
                * (vt - seen)[:, None, :]
            state = state.astype(state_dtype)
            return state, jnp.einsum("hk,hkv->hv", qt, state.astype(F32))

        last, o = jax.lax.scan(token, jnp.zeros((H, D, D), state_dtype),
                               (q, k, v, alpha, beta))

        def out(t):
            ot, gt = t[:, :H * D].reshape(-1, H, D), t[:, H * D:]
            ot = _rms(ot, lw["o_norm"], eps).reshape(-1, H * D)
            return (ot * _sigmoid(gt)) @ lw["o"].astype(F32)

        return x + _by_chunks(out, jnp.concatenate(
            [o.reshape(S, H * D), gate], -1)), last


# -------------------------------------------------------------------- MLA

@functools.partial(jax.jit, static_argnames=("key",))
def _mla(x, lw, key):
    cfg = dict(key)
    with jax.default_matmul_precision("highest"):
        H, dn, dr, dv = (cfg["num_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
        rkv, eps, theta = (cfg["kv_lora_rank"], cfg["rms_norm_eps"],
                           cfg["rope_theta"])
        S = x.shape[0]
        pos = jnp.arange(S)
        scale = np.float32((dn + dr) ** -0.5)

        def keys_values(t):                  # t: [chunk, d + 1], pos last
            a = _rms(t[:, :-1], lw["input_norm"], eps)
            kv_a = a @ lw["kv_a"].astype(F32)
            c = _rms(kv_a[:, :rkv], lw["kv_a_norm"], eps)
            return jnp.concatenate(
                [c @ lw["kv_b"].astype(F32),
                 _rope(kv_a[:, rkv:], t[:, -1], theta)], -1)

        xp = jnp.concatenate([x, pos.astype(F32)[:, None]], -1)
        kv = _by_chunks(keys_values, xp)
        k_rope = kv[:, H * (dn + dv):]                           # [S, dr]
        kv = kv[:, :H * (dn + dv)].reshape(S, H, dn + dv).transpose(1, 0, 2)

        def block(t):                        # QUERY_BLOCK queries
            xb, pb = t[:, :-1], t[:, -1]
            a = _rms(xb, lw["input_norm"], eps)
            q = (a @ lw["q"].astype(F32)).reshape(-1, H, dn + dr)
            q_rope = _rope(q[..., dn:], pb, theta)
            seen = pos[None, :] <= pb.astype(jnp.int32)[:, None]

            def head(c):                     # one head: its [bq, S] scores
                qn, qr, kvh = c
                s = (qn @ kvh[:, :dn].T + qr @ k_rope.T) * scale
                p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
                return p @ kvh[:, dn:]

            o = jax.lax.map(head, (q[..., :dn].transpose(1, 0, 2),
                                   q_rope.transpose(1, 0, 2), kv))
            o = o.transpose(1, 0, 2) \
                * _sigmoid(a @ lw["gate"].astype(F32))[:, :, None]
            return xb + o.reshape(-1, H * dv) @ lw["o"].astype(F32)

        return _by_chunks(block, xp, QUERY_BLOCK)


# -------------------------------------------------------------------- FFN

@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(h, post_norm, w13, w2, eps):
    with jax.default_matmul_precision("highest"):
        return h + _by_chunks(
            lambda t: _gated(_rms(t, post_norm, eps), w13, w2), h)


def route(u, gate_w, gate_b, top_k, n_group, topk_group, norm_topk_prob,
          scaling):
    """-> (expert ids [S, k], weights [S, k]) over ALL experts."""
    s = _sigmoid(u @ gate_w.astype(F32))
    pick = s + gate_b.astype(F32)[None, :]
    S, E = pick.shape
    if n_group > 1:
        groups = pick.reshape(S, n_group, E // n_group)
        score = jax.lax.top_k(groups, 2)[0].sum(-1)          # [S, groups]
        _, kept = jax.lax.top_k(score, topk_group)
        keep = jnp.zeros((S, n_group), bool).at[
            jnp.arange(S)[:, None], kept].set(True)
        pick = jnp.where(jnp.repeat(keep, E // n_group, axis=1), pick,
                         -jnp.inf)
    _, idx = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(s, idx, -1)
    if norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    return idx, w * scaling


@functools.partial(jax.jit, static_argnames=("key",))
def _moe_ffn(h, post_norm, ffn, key):
    cfg = dict(key)
    with jax.default_matmul_precision("highest"):
        u = _rms(h, post_norm, cfg["rms_norm_eps"])
        idx, g = route(u, ffn["gate_w"], ffn["gate_b"],
                       cfg["num_experts_per_tok"], cfg["n_group"],
                       cfg["topk_group"], cfg["norm_topk_prob"],
                       cfg["routed_scaling_factor"])
        lo, hi = cfg["experts_held"]
        y = _by_chunks(lambda t: _gated(t, ffn["shared_w13"],
                                        ffn["shared_w2"]), u) \
            if "shared_w13" in ffn else jnp.zeros_like(u)

        def expert(carry, ew):
            e, w13, w2 = ew
            # this expert's weight for every token (0 where not chosen)
            ge = jnp.where(idx == e, g, 0.0).sum(-1)
            out = _by_chunks(lambda t: _gated(t, w13, w2), u)
            return carry + ge[:, None] * out, None

        routed, _ = jax.lax.scan(
            expert, jnp.zeros_like(u),
            (jnp.arange(lo, hi), ffn["w13"], ffn["w2"]))
        return h + y + routed


def hidden(weights, ids, state_dtype=F32, states=None):
    """ids [S] int -> the last layer's output [S, d], float32. A list
    handed in as ``states`` gains each KDA layer's state [H, D, D] after
    the sequence's last token (so hand in no padding then)."""
    cfg = weights["cfg"]
    key = _cfg_key(cfg)
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(F32)
    for lw in weights["layers"]:
        mix = {k: v for k, v in lw.items()
               if k not in ("kind", "ffn", "post_norm")}
        if lw["kind"] == "kda":
            h, last = _kda(x, mix, key, jnp.dtype(state_dtype))
            if states is not None:
                states.append(last)
        else:
            h = _mla(x, mix, key)
        ffn = lw["ffn"]
        if "gate_w" in ffn:
            x = _moe_ffn(h, lw["post_norm"], ffn, key)
        else:
            x = _dense_ffn(h, lw["post_norm"], ffn["w13"], ffn["w2"],
                           cfg["rms_norm_eps"])
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(rows, norm, lm_head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(rows, norm, eps) @ lm_head.astype(F32)


def logits_at(weights, ids, positions, state_dtype=F32):
    """ids [S] -> logits [len(positions), V] float32 at those positions of
    the one sequence (right padding after the last position asked for is
    harmless: every layer is causal). V is the vocabulary slice the
    weights hold."""
    x = hidden(weights, ids, state_dtype)
    rows = x[jnp.asarray(positions, jnp.int32)]
    return _head(rows, weights["norm"], weights["lm_head"],
                 weights["cfg"]["rms_norm_eps"])
