"""Plain reference for the GPT family: the forward pass and the loss in
straightforward ``jax.numpy``, float32 with
``jax.default_matmul_precision("highest")``, written from the equations of
Radford et al. 2019 (GPT-2) as GPT-3 (Brown et al. 2020, section 2.1) uses
them: learned token and position embeddings, pre-LayerNorm blocks of
causal multi-head attention and a 4x GELU MLP, a final LayerNorm, and the
output head tied to the token embedding.

No kernel, no cache, no batching tricks, and nothing imported from the
program under test. It is handed weights as arrays (any float dtype, on
one device or sharded over a mesh) and upcasts one block at a time, so it
fits beside a training state.

Departures from the paper, each because the program under test makes the
same one and the comparison is of mathematics, not of checkpoints:

* every layer attends densely; GPT-3 alternates dense and locally banded
  sparse layers (the paper gives no band width);
* GELU is the tanh approximation, as in GPT-2's released code;
* LayerNorm's epsilon is 1e-5.

Weights arrive as::

    {"wte": [V, h], "wpe": [P, h], "ln_f": (g, b),
     "blocks": [{"ln1": (g, b), "wqkv": [h, 3h], "bqkv": [3h],
                 "wo": [h, h], "bo": [h], "ln2": (g, b),
                 "w1": [h, f], "b1": [f], "w2": [f, h], "b2": [h]}, ...],
     "num_heads": H}

with matrices stored ``[in, out]`` and the fused qkv columns laid out
``[q | k | v]``, heads contiguous inside each.
"""
import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS = 1e-5


def _ln(x, gb):
    g, b = gb
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * g.astype(F32) + b.astype(F32)


def _gelu(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("num_heads",))
def _block(x, blk, num_heads):
    """x [B, S, h] float32 -> [B, S, h] float32."""
    with jax.default_matmul_precision("highest"):
        b, s, h = x.shape
        d = h // num_heads
        y = _ln(x, blk["ln1"])
        qkv = y @ blk["wqkv"].astype(F32) + blk["bqkv"].astype(F32)
        q, k, v = (t.reshape(b, s, num_heads, d)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + att.reshape(b, s, h) @ blk["wo"].astype(F32) \
            + blk["bo"].astype(F32)
        y = _ln(x, blk["ln2"])
        y = _gelu(y @ blk["w1"].astype(F32) + blk["b1"].astype(F32))
        return x + y @ blk["w2"].astype(F32) + blk["b2"].astype(F32)


@jax.jit
def _embed(wte, wpe, ids):
    s = ids.shape[1]
    return wte[ids].astype(F32) + wpe[:s].astype(F32)[None]


@jax.jit
def _head(x, ln_f, wte):
    """Final norm and tied output head: x [..., h] -> logits [..., V]."""
    with jax.default_matmul_precision("highest"):
        return _ln(x, ln_f) @ wte.astype(F32).T


@jax.jit
def _token_nll(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def hidden(weights, ids):
    """ids [B, S] int -> the last block's output [B, S, h], float32."""
    x = _embed(weights["wte"], weights["wpe"], jnp.asarray(ids, jnp.int32))
    for blk in weights["blocks"]:
        x = _block(x, blk, num_heads=weights["num_heads"])
    return x


def loss(weights, ids, labels):
    """Mean next-token cross entropy over all B*S positions, as a float.
    One sequence's logits at a time: [S, V] float32 is what has to fit."""
    x = hidden(weights, ids)
    labels = jnp.asarray(labels, jnp.int32)
    total = 0.0
    for i in range(x.shape[0]):
        nll = _token_nll(_head(x[i], weights["ln_f"], weights["wte"]),
                         labels[i])
        total += float(nll.sum())
    return total / (x.shape[0] * x.shape[1])


def logits_at(weights, ids, positions):
    """ids [S] -> logits [len(positions), V] float32 at those positions of
    the one sequence (right padding after the last position asked for is
    harmless: attention is causal)."""
    x = hidden(weights, jnp.asarray(ids, jnp.int32)[None])[0]
    rows = x[jnp.asarray(positions, jnp.int32)]
    return _head(rows, weights["ln_f"], weights["wte"])
