"""What the decoders of ``mla_moe.py`` and ``afmoe.py`` share: the RMS
norm on raw arrays, the gated-SiLU MLP, per-token positions, the mask of
a ragged round's real tokens, and greedy decoding over a dense cache."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..incubate.moe import gated_silu

__all__ = ["rms", "GatedMLP", "positions", "valid_tokens",
           "greedy_generate"]


def rms(x, w, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


class GatedMLP(nn.Layer):
    """``W2 (silu(Wg u) * Wu u)``, gate and up projections side by side
    in ``w13``; ``cfg`` gives ``hidden_size`` and ``dtype``."""

    def __init__(self, cfg, width):
        super().__init__()
        from ..nn import initializer as I
        d = cfg.hidden_size
        self.width = int(width)
        self.w13 = self.create_parameter(
            [d, 2 * self.width], dtype=cfg.dtype,
            default_initializer=I.Normal(0.0, d ** -0.5))
        self.w2 = self.create_parameter(
            [self.width, d], dtype=cfg.dtype,
            default_initializer=I.Normal(0.0, self.width ** -0.5))

    def forward(self, x):
        return apply("gated_silu_mlp",
                     lambda a, w13, w2: jnp.matmul(
                         gated_silu(jnp.matmul(a, w13), self.width), w2),
                     [x, self.w13, self.w2])


def positions(pos_offset, s):
    """``forward``'s ``pos_offset`` as int32 positions ``[B or 1, s]``:
    per-token positions (a ragged round), one offset a row, one offset,
    or a plain number."""
    from .. import ops
    if isinstance(pos_offset, Tensor) and len(pos_offset.shape) == 2:
        return pos_offset.astype("int32")
    if isinstance(pos_offset, Tensor) and len(pos_offset.shape) == 1:
        return pos_offset.astype("int32").unsqueeze(1) \
            + ops.arange(s, dtype="int32").unsqueeze(0)
    if isinstance(pos_offset, Tensor):
        return (ops.arange(s, dtype="int32")
                + pos_offset.astype("int32")).unsqueeze(0)
    return ops.arange(pos_offset, pos_offset + s,
                      dtype="int32").unsqueeze(0)


def valid_tokens(cache, total):
    """Which tokens of a ragged round's flat stream are real."""
    def fwd(rs, rl, kl):
        from ..ops.pallas.ragged_attention import ragged_row_index
        return ragged_row_index(rs, rl, kl, total)[2]
    return apply("ragged_valid_tokens", fwd,
                 [cache["row_starts"], cache["row_lens"], cache["kv_lens"]])


def greedy_generate(model, input_ids, max_new_tokens, eos_token_id, caches):
    """Greedy decoding with the model's dense cache (``caches``: one
    empty dict of its rows a layer): the prompt in one forward, then a
    token at a time. -> ids [B, prompt + new]."""
    from .. import ops
    from ..core.autograd import no_grad
    if input_ids.shape[1] + max_new_tokens > model.config.max_seq_len:
        raise ValueError(
            f"prompt ({input_ids.shape[1]}) + max_new_tokens "
            f"({max_new_tokens}) exceeds max_seq_len "
            f"({model.config.max_seq_len})")
    was_training = model.training
    model.eval()
    try:
        with no_grad():
            out, cur = input_ids, input_ids.shape[1]
            logits = model(input_ids, caches=caches)
            for _ in range(max_new_tokens):
                nxt = ops.argmax(logits[:, -1], axis=-1,
                                 keepdim=True).astype(input_ids.dtype)
                out = ops.concat([out, nxt], axis=1)
                if eos_token_id is not None and bool(
                        jnp.all(nxt._data == eos_token_id)):
                    break
                logits = model(nxt, caches=caches, pos_offset=cur)
                cur += 1
            return out
    finally:
        if was_training:
            model.train()
