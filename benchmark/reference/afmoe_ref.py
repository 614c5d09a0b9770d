"""Plain reference for the ``afmoe`` family (Arcee's Trinity models run it):
straightforward ``jax.numpy``, float32 with
``jax.default_matmul_precision("highest")``, written from the equations of
the public ``modeling_afmoe.py`` as ISSUE 33 states them.

No kernel, no cache, no batching, and nothing imported from the program
under test: every position attends over all positions of the one sequence
under the layer's mask. Weights arrive in whatever float type the program
holds and are upcast one matrix (one expert) at a time; attention runs in
blocks of queries and one KV head at a time, the MLPs in slices of tokens
and the experts one after the other, so that an 18,432-token sequence fits
beside a serving engine. Blocking changes no number: a query's softmax is
over its whole row.

    h = E[ids] * embed_scale                       (sqrt(hidden), mup)
    per layer, N* RMS norms with a learned scale:
      a = N_input(h)
      q = N_q(a Wq) [H, D];  k = N_k(a Wk), v = a Wv [KVH, D];  g = a Wg
      a SLIDING layer (window W) turns q and k by RoPE (theta over the
      whole D, halves rotated against each other: pair (i, i + D/2) by
      theta^(-2i/D)) and lets token i see j where 0 <= i - j < W;
      a FULL layer uses no positions and sees every j <= i
      o = softmax(q k^T / sqrt(D)) v   KV head c read by heads cG..cG+G-1
      h = h + N_post_attention((o * sigmoid(g)) Wo)
      h = h + N_post_mlp(f(N_pre_mlp(h)))
    logits = N_final(h) W_head

f, dense layer:  W2 (silu(Wg' m) * (Wu m)).  Expert layer:
s = sigmoid(m Wr) over ALL experts; the k experts with the largest s + b
(b selects only); w = s[chosen] / (sum + 1e-20) * route_scale;
f(m) = Shared(m) + sum_{e chosen, e held} w_e Expert_e(m). Only the experts
in ``experts_held`` contribute: the reference leaves out what the program
leaves out (one expert-parallel rank's share).

Weights arrive as::

    {"cfg": {num_heads, num_kv_heads, head_dim, windows (one a layer: W
             or None), num_experts_per_tok, route_norm, route_scale,
             experts_held (lo, hi), rms_norm_eps, rope_theta, embed_scale},
     "embed": [V, d], "norm": [d], "lm_head": [d, V],
     "layers": [{"input_norm", "post_attention_norm", "pre_mlp_norm",
                 "post_mlp_norm": [d], "q_norm", "k_norm": [D],
                 "qkvg": [d, (2H + 2KVH) D]  columns [q | k | v | g],
                 "o": [H D, d],
                 "ffn": {"w13": [d, 2f], "w2": [f, d]}  or
                        {"gate_w": [d, E], "gate_b": [E],
                         "w13": [held, d, 2m], "w2": [held, m, d],
                         "shared_w13": [d, 2ms], "shared_w2": [ms, d]}}]}

with matrices stored ``[in, out]`` and gate and up projections side by side
(``[gate | up]``).
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
    """x [S, heads, D], pos [S]: halves rotated against each other."""
    d = x.shape[-1]
    f = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None, None] * f
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _cfg_key(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in cfg.items()))


@functools.partial(jax.jit, static_argnames=("key", "window"))
def _attention(x, lw, key, window):
    cfg = dict(key)
    with jax.default_matmul_precision("highest"):
        H, KVH, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
        G, eps, theta = H // KVH, cfg["rms_norm_eps"], cfg["rope_theta"]
        S = x.shape[0]
        w = lw["qkvg"]
        cut = [0, H * D, (H + KVH) * D, (H + 2 * KVH) * D,
               (2 * H + 2 * KVH) * D]
        wq, wk, wv, wg = (w[:, a:b] for a, b in zip(cut, cut[1:]))
        pos = jnp.arange(S)

        def keys_values(t):                  # t: [chunk, d + 1], pos last
            a = _rms(t[:, :-1], lw["input_norm"], eps)
            k = _rms((a @ wk.astype(F32)).reshape(-1, KVH, D),
                     lw["k_norm"], eps)
            if window is not None:
                k = _rope(k, t[:, -1], theta)
            return jnp.concatenate(
                [k.reshape(-1, KVH * D), a @ wv.astype(F32)], -1)

        xp = jnp.concatenate([x, pos.astype(F32)[:, None]], -1)
        kv = _by_chunks(keys_values, xp)
        k = kv[:, :KVH * D].reshape(S, KVH, D).transpose(1, 0, 2)
        v = kv[:, KVH * D:].reshape(S, KVH, D).transpose(1, 0, 2)

        def block(t):                        # QUERY_BLOCK queries
            xb, pb = t[:, :-1], t[:, -1].astype(jnp.int32)
            a = _rms(xb, lw["input_norm"], eps)
            q = _rms((a @ wq.astype(F32)).reshape(-1, H, D),
                     lw["q_norm"], eps)
            if window is not None:
                q = _rope(q, pb, theta)
            seen = pos[None, :] <= pb[:, None]
            if window is not None:
                seen &= pb[:, None] - pos[None, :] < window
            seen = jnp.repeat(seen, G, axis=0)              # [bq * G, S]

            def head(c):                     # one KV head, its G heads
                qc, kc, vc = c
                s = (qc.reshape(-1, D) @ kc.T) / np.sqrt(D).astype(F32)
                p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
                return (p @ vc).reshape(-1, G, D)

            o = jax.lax.map(head, (
                q.reshape(-1, KVH, G, D).transpose(1, 0, 2, 3), k, v))
            o = o.transpose(1, 0, 2, 3).reshape(-1, H * D)
            o = o * jax.nn.sigmoid(a @ wg.astype(F32))
            return xb + _rms(o @ lw["o"].astype(F32),
                             lw["post_attention_norm"], eps)

        return _by_chunks(block, xp, QUERY_BLOCK)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(h, pre, post, w13, w2, eps):
    with jax.default_matmul_precision("highest"):
        return h + _by_chunks(
            lambda t: _rms(_gated(_rms(t, pre, eps), w13, w2), post, eps),
            h)


def route(m, gate_w, gate_b, top_k, route_norm, route_scale):
    """-> (expert ids [S, k], weights [S, k]) over ALL experts."""
    s = jax.nn.sigmoid(m @ gate_w.astype(F32))
    _, idx = jax.lax.top_k(s + gate_b.astype(F32)[None, :], top_k)
    w = jnp.take_along_axis(s, idx, -1)
    if route_norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * route_scale


@functools.partial(jax.jit, static_argnames=("key",))
def _moe_ffn(h, pre, post, ffn, key):
    cfg = dict(key)
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        m = _rms(h, pre, eps)
        idx, w = route(m, ffn["gate_w"], ffn["gate_b"],
                       cfg["num_experts_per_tok"], cfg["route_norm"],
                       cfg["route_scale"])
        lo, hi = cfg["experts_held"]
        y = _by_chunks(lambda t: _gated(t, ffn["shared_w13"],
                                        ffn["shared_w2"]), m) \
            if "shared_w13" in ffn else jnp.zeros_like(m)

        def expert(carry, ew):
            e, w13, w2 = ew
            # this expert's weight for every token (0 where not chosen)
            we = jnp.where(idx == e, w, 0.0).sum(-1)
            out = _by_chunks(lambda t: _gated(t, w13, w2), m)
            return carry + we[:, None] * out, None

        routed, _ = jax.lax.scan(
            expert, jnp.zeros_like(m),
            (jnp.arange(lo, hi), ffn["w13"], ffn["w2"]))
        return h + _rms(y + routed, post, eps)


def hidden(weights, ids):
    """ids [S] int -> the last layer's output [S, d], float32."""
    cfg = weights["cfg"]
    key = _cfg_key({k: v for k, v in cfg.items() if k != "windows"})
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(F32) \
        * np.float32(cfg["embed_scale"])
    for lw, window in zip(weights["layers"], cfg["windows"]):
        attn = {k: v for k, v in lw.items()
                if k not in ("ffn", "pre_mlp_norm", "post_mlp_norm")}
        h = _attention(x, attn, key, window)
        ffn = lw["ffn"]
        if "gate_w" in ffn:
            x = _moe_ffn(h, lw["pre_mlp_norm"], lw["post_mlp_norm"], ffn,
                         key)
        else:
            x = _dense_ffn(h, lw["pre_mlp_norm"], lw["post_mlp_norm"],
                           ffn["w13"], ffn["w2"], cfg["rms_norm_eps"])
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(rows, norm, lm_head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(rows, norm, eps) @ lm_head.astype(F32)


def logits_at(weights, ids, positions):
    """ids [S] -> logits [len(positions), V] float32 at those positions of
    the one sequence (right padding after the last position asked for is
    harmless: attention is causal). V is the vocabulary slice the weights
    hold."""
    x = hidden(weights, ids)
    rows = x[jnp.asarray(positions, jnp.int32)]
    return _head(rows, weights["norm"], weights["lm_head"],
                 weights["cfg"]["rms_norm_eps"])
