"""GPT — flagship decoder-only transformer with hybrid-parallel shardings.

Reference: test/auto_parallel/get_gpt_model.py + the fleet GPT recipes the
BASELINE configs 3/4 target (mp×pp×dp×sharding via
fleet/meta_parallel/*). TPU-native: tensor parallel comes from the
fleet TP layers (weights sharded over 'model'), sequence parallel from
sharding constraints on the residual stream over 'sep', data parallel from
batch sharding over 'data', ZeRO from optimizer-state sharding over
'sharding' — all composed in one mesh, compiled by GSPMD into a single SPMD
program per train step.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..distributed.fleet.recompute import (choose_keep, device_free_bytes,
                                          keep_name, recompute)
from ..nn import functional as F
from ..observability import tracing as _trc

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTPretrainingCriterion", "gpt_tiny", "gpt_small", "gpt_1p3b",
           "gpt_13b"]


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None, max_seq_len=1024,
                 dropout=0.1, layer_norm_epsilon=1e-5, tensor_parallel=False,
                 sequence_parallel=False, use_rms_norm=False,
                 tie_word_embeddings=True, recompute=False,
                 tp_overlap=None, num_kv_heads=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        # grouped-query attention: num_kv_heads < num_heads shares each K/V
        # head across a group of num_heads // num_kv_heads query heads —
        # KV caches (dense AND paged serving pools) shrink by that factor,
        # which directly raises how many concurrent requests a serving
        # pool can hold. Default (None) = multi-head attention.
        self.num_kv_heads = int(num_kv_heads or num_heads)
        if num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={num_heads} must be divisible by "
                f"num_kv_heads={self.num_kv_heads} (query heads are "
                "grouped evenly over KV heads)")
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.layer_norm_epsilon = layer_norm_epsilon
        self.tensor_parallel = tensor_parallel
        self.sequence_parallel = sequence_parallel
        self.use_rms_norm = use_rms_norm
        self.tie_word_embeddings = tie_word_embeddings
        self.recompute = recompute
        # latency-hiding TP matmul+collective decomposition (overlap
        # engine): None = auto behind the measured ab_gate verdict at the
        # exact shape (never off-TPU), True = force, False = plain fused
        self.tp_overlap = tp_overlap


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=64, dropout=0.0, **kw)


def gpt_small(**kw):
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt_1p3b(**kw):
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16, **kw)


def gpt_13b(**kw):
    return GPTConfig(hidden_size=5120, num_layers=40, num_heads=40, **kw)


def block_keep_bytes(config, tokens, itemsize, model_deg=1):
    """Bytes on a device of the tensors one block tags for its backward
    (``keep_name``; ``attn_out`` is the flash kernel's output and row
    statistics, tagged in its own forward rule), for ``tokens`` tokens of
    ``itemsize`` bytes a value on that device. In falling order of what a
    byte saved on the chip (PERF.md, PR 32): ``attn_res`` an output
    projection and, under tensor parallelism, its all-reduce; ``fc1`` its
    matmul; ``attn_out`` the attention kernel's forward. ``fc1`` and the
    heads are split over 'model'; the residual stream is whole. (The fused
    qkv projection's output is not among them: kept, it cost the step as
    much as its matmul saved.)"""
    h = config.hidden_size
    return {
        "attn_res": tokens * h * itemsize,
        "fc1": tokens * config.intermediate_size // model_deg * itemsize,
        "attn_out": tokens * (h * itemsize + config.num_heads * 4)
        // model_deg,
    }


# what the allocator must keep free beyond the compiler's count of a step:
# the runtime's own buffers and fragmentation (15.0 of a v5e's 15.75 GiB;
# PR 24 met a step that died at 14.9)
_RUNTIME_HEADROOM = 3 << 28


def step_reserve_bytes(config, tokens, itemsize, model_deg=1):
    """What a training step under full recompute needs on a device, beyond
    its parameters and optimizer state, at the moment every block's kept
    tensors are alive: between the last block's forward and its backward.
    Each block's input, one block's full activations, the [tokens, vocab]
    logits in float32 with their gradient and the copy the loss reads, and
    the runtime's headroom. (The step's other high-water mark, the end of
    the backward with every gradient in hand, holds no kept tensor: one
    that fits under full recompute fits with them. Calibrated against the
    TPU compiler's count, ``benchmark/tools/compile_keep_v5e.py``: 2.43
    GiB here against 2.28 counted at GPT-3 1.3B widths on one chip.)"""
    h, ffn = config.hidden_size, config.intermediate_size
    block_inputs = config.num_layers * tokens * h * itemsize
    one_block = tokens * (6 * h + (3 * h + 2 * ffn) // model_deg) * itemsize
    logits = tokens * config.vocab_size // model_deg * (4 + 4 + itemsize)
    return block_inputs + one_block + logits + _RUNTIME_HEADROOM


def _cache_write(buf, new, ln):
    """Write `new` [B, s, H, Dh] into `buf` at sequence offset `ln` (a
    python int or traced int32 scalar) — fixed output shape for compiled
    decode."""
    def fwd(b, n, l):
        return jax.lax.dynamic_update_slice(
            b, n.astype(b.dtype),
            (jnp.zeros((), jnp.int32), l.astype(jnp.int32).reshape(()),
             jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)))
    return apply("kv_cache_write", fwd, [buf, new, ln])


def _ids_write(buf, new, col):
    """Write `new` [B, 1] into `buf` [B, T] at column `col` (traced)."""
    def fwd(b, n, c):
        return jax.lax.dynamic_update_slice(
            b, n.astype(b.dtype),
            (jnp.zeros((), jnp.int32), c.astype(jnp.int32).reshape(())))
    return apply("ids_write", fwd, [buf, new, col])


def _write_pools(cache, rows, write, *where):
    """Scatter this layer's new rows (name -> Tensor) into its own pools
    with one of ``serving/kv_cache.py``'s writers; the cache dict then
    holds the updated pools. -> the raw-array view an attention impl
    takes."""
    pools = cache["pools"]
    for name, new in rows.items():
        pools[name] = write(pools[name], new, *where)
    return pools


def _ragged_attend(q, pools, block_tables, row_starts, row_lens,
                   kv_lens, impl):
    """Ragged paged attention over the flat stream `q` [1, T, H, Dh]:
    token t attends causally over its OWN row's pages up to its absolute
    position (its K/V was just written: write, then attend). `impl`
    runs on raw arrays — the serving tier injects the A/B-gated /
    KV-head-sharded variant."""
    names = list(pools)

    def fwd(qa, bta, rs, rl, kl, *pa):
        out = impl(qa[0], dict(zip(names, pa)), rs.astype(jnp.int32),
                   rl.astype(jnp.int32), kl.astype(jnp.int32),
                   bta.astype(jnp.int32))
        return out[None]
    return apply("ragged_attention", fwd,
                 [q, block_tables, row_starts, row_lens, kv_lens]
                 + [pools[n] for n in names])


def _default_impl():
    """The XLA reference over ``{"k", "v"}`` pools, for a caller that
    injected no attention of its own."""
    from ..ops.pallas.ragged_attention import \
        ragged_paged_attention_reference as _ragged
    return lambda q, p, *meta: _ragged(q, p["k"], p["v"], *meta)


def _flash_constrain(x):
    """Constrain a [B, S, H, Dh] attention operand to the sharded-flash
    layout, the shard_map's in_spec (snippet [2]): heads over 'model',
    batch over every mesh axis that splits it (``flash_batch_axes``:
    'data' and 'sharding'). Naming them all is what keeps collectives out
    of the attention block: an axis the batch is split over and the spec
    leaves out means q, k and v are gathered across it."""
    from ..distributed.topology import get_hybrid_communicate_group
    from ..ops.pallas.flash_attention import flash_batch_axes
    mesh = get_hybrid_communicate_group().mesh
    spec = P(flash_batch_axes(mesh, x.shape[0]) or None, None, "model", None)
    return apply("flash_shard_constraint",
                 lambda a: jax.lax.with_sharding_constraint(
                     a, NamedSharding(mesh, spec)), [x])


def _sp_constrain(x, sequence_parallel):
    """Shard the [B, S, H] residual stream: batch over 'data', seq over
    'sep' (sequence/context parallel; SURVEY §5 long-context). Decode
    steps (seq not divisible by the sep degree, e.g. one token) keep the
    batch sharding only."""
    if not sequence_parallel:
        return x
    from ..distributed.topology import get_hybrid_communicate_group
    hcg = get_hybrid_communicate_group()
    sep = hcg.mesh.shape.get("sep", 1)
    spec = P("data", "sep", None) if x.shape[1] % sep == 0 else \
        P("data", None, None)
    return apply("sp_constraint", lambda a: jax.lax.with_sharding_constraint(
        a, NamedSharding(hcg.mesh, spec)), [x])


class GPTAttention(nn.Layer):
    # test hook: swap the per-shard attention impl (the CPU mesh cannot
    # run the real Pallas kernel, interpret mode is not a measurement)
    _sharded_impl_override = None

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_heads
        self.num_kv_heads = config.num_kv_heads
        self.head_dim = config.hidden_size // config.num_heads
        self.dropout = config.dropout
        self._tp = config.tensor_parallel
        self._sharded_fa = None  # (mesh id, shard_map'd kernel) cache
        h = config.hidden_size
        # fused QKV: [q (H·Dh) | k (KVH·Dh) | v (KVH·Dh)] — collapses to
        # the classic 3h projection when num_kv_heads == num_heads
        qkv_out = h + 2 * self.num_kv_heads * self.head_dim
        if config.tensor_parallel:
            from ..distributed import fleet
            self.qkv_proj = fleet.ColumnParallelLinear(h, qkv_out,
                                                       gather_output=False)
            self.out_proj = fleet.RowParallelLinear(
                h, h, input_is_parallel=True,
                tp_overlap=config.tp_overlap)
        else:
            self.qkv_proj = nn.Linear(h, qkv_out)
            self.out_proj = nn.Linear(h, h)

    def _expand_kv(self, t):
        """Broadcast each KV head over its query-head group for the dense
        attention paths ([B, S, KVH, Dh] -> [B, S, H, Dh]); the paged
        serving path attends grouped instead (no expansion — that is the
        GQA memory/bandwidth win)."""
        groups = self.num_heads // self.num_kv_heads
        if groups == 1:
            return t
        from .. import ops
        return ops.repeat_interleave(t, groups, axis=2)

    def _sharded_flash(self, q, k):
        """The shard_map'd flash kernel for the training path (SNIPPETS
        [1]–[3]): heads over the mesh 'model' axis, batch over the axes
        that split it ('data', 'sharding') — or None when ineligible (no
        TP mesh, indivisible heads, mask/dropout active, kernel demoted
        by the A/B gate). Built once per mesh and cached; it picks the
        batch axes from the batch it is traced with."""
        if not self._tp:
            return None
        override = GPTAttention._sharded_impl_override
        if override is None:
            from ..nn.functional.common import _flash_eligible
            if not _flash_eligible(q, k, None, self.dropout, self.training,
                                   True):
                return None
        try:
            from ..distributed.topology import get_hybrid_communicate_group
            mesh = get_hybrid_communicate_group().mesh
        except Exception:
            return None
        from ..ops.pallas.flash_attention import (flash_batch_axes,
                                                  sharded_flash_attention)
        m_deg = int(mesh.shape.get("model", 1))
        b, _, h, _ = q.shape
        if h % m_deg or (m_deg <= 1 and not flash_batch_axes(mesh, b)):
            return None  # single shard: F.sdpa already picks the kernel
        cached = self._sharded_fa
        if cached is not None and cached[0] == id(mesh):
            return cached[1]
        fa = sharded_flash_attention(mesh, causal=True, impl=override)
        self._sharded_fa = (id(mesh), fa)
        return fa

    def forward(self, x, cache=None):
        """cache (decode): dict with 'k'/'v' Tensors [B, T, H, Dh] that new
        keys/values are appended to (reference: fused multi-head attention
        cache_kv semantics)."""
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        h_q = self.num_heads * self.head_dim
        kv_w = self.num_kv_heads * self.head_dim
        q = qkv[:, :, :h_q].reshape([b, s, self.num_heads, self.head_dim])
        k = qkv[:, :, h_q:h_q + kv_w].reshape(
            [b, s, self.num_kv_heads, self.head_dim])
        v = qkv[:, :, h_q + kv_w:].reshape(
            [b, s, self.num_kv_heads, self.head_dim])
        if cache is not None and cache.get("static"):
            # fixed-shape KV buffers [B, T, H, Dh] + a traced write cursor:
            # the whole decode step keeps one shape, so lax.while_loop can
            # carry it (compiled generate; reference capability:
            # block_multihead_attention's preallocated cache_kv)
            from .. import ops
            ln = cache["len"]          # int32 scalar Tensor: tokens cached
            kbuf = _cache_write(cache["k"], k, ln)
            vbuf = _cache_write(cache["v"], v, ln)
            cache["k"], cache["v"] = kbuf, vbuf
            cache["len"] = ln + s
            T = kbuf.shape[1]
            # key j visible to query i (at absolute pos ln+i) iff j <= ln+i
            key_pos = ops.arange(T, dtype="int32").unsqueeze(0)    # [1,T]
            q_pos = (ops.arange(s, dtype="int32") + ln).unsqueeze(1)
            mask = (key_pos <= q_pos).reshape([1, 1, s, T])
            out = F.scaled_dot_product_attention(
                q, self._expand_kv(kbuf), self._expand_kv(vbuf),
                attn_mask=mask, dropout_p=0.0, training=False)
        elif cache is not None and cache.get("ragged"):
            # ragged serving round (ONE launch for the whole scheduler
            # round — Ragged Paged Attention shape): x is the FLAT token
            # stream [1, T, h]; per-row metadata maps each token to its
            # row's pages and absolute position. K/V scatter and the
            # ragged attention happen in the same program, so mixed
            # decode rows + prefill chunks share one launch with no
            # bucket padding beyond the padded T itself.
            rs = cache["row_starts"]            # [R] int32
            rl = cache["row_lens"]              # [R] int32
            kl = cache["kv_lens"]               # [R] int32 (post-write)
            bt = cache["block_tables"]          # [R, max_pages] int32
            from ..serving import kv_cache as _kvc
            pools = _write_pools(cache, {"k": k, "v": v},
                                 _kvc.pool_write_ragged, bt, rs, rl, kl)
            impl = cache.get("attn_impl") or _default_impl()
            out = _ragged_attend(q, pools, bt, rs, rl, kl, impl)
        elif cache is not None:
            from .. import ops
            if cache.get("k") is not None:
                if s != 1:
                    raise NotImplementedError(
                        "cached attention appends one token at a time "
                        "after the prefill pass")
                k = ops.concat([cache["k"], k], axis=1)
                v = ops.concat([cache["v"], v], axis=1)
            cache["k"], cache["v"] = k, v
            causal = s > 1  # prefill is causal; single-token decode
            out = F.scaled_dot_product_attention(
                q, self._expand_kv(k), self._expand_kv(v),
                is_causal=causal, dropout_p=0.0, training=False)
        else:
            # training/no-cache: dense attention over H query heads — KV
            # heads broadcast over their groups up front so the flash /
            # sdpa kernels see the classic equal-head layout
            k, v = self._expand_kv(k), self._expand_kv(v)
            fa = self._sharded_flash(q, k)
            if fa is not None:
                # explicit placement before the manually-partitioned
                # kernel (snippet [3]): q/k/v constrained to the
                # shard_map in_specs so GSPMD never reshards around it
                q, k, v = (_flash_constrain(t) for t in (q, k, v))
                out = apply("sharded_flash_attention", fa, [q, k, v])
            else:
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, dropout_p=self.dropout,
                    training=self.training)
        out = out.reshape([b, s, h])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, ffn = config.hidden_size, config.intermediate_size
        if config.tensor_parallel:
            from ..distributed import fleet
            self.fc1 = fleet.ColumnParallelLinear(h, ffn,
                                                  gather_output=False)
            self.fc2 = fleet.RowParallelLinear(
                ffn, h, input_is_parallel=True,
                tp_overlap=config.tp_overlap)
        else:
            self.fc1 = nn.Linear(h, ffn)
            self.fc2 = nn.Linear(ffn, h)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x):
        y = keep_name(self.fc1(x), "fc1")
        return self.dropout(self.fc2(F.gelu(y, approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        norm = nn.RMSNorm if config.use_rms_norm else nn.LayerNorm
        self.ln_1 = norm(config.hidden_size,
                         epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = norm(config.hidden_size,
                         epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.dropout = nn.Dropout(config.dropout)
        self._sp = config.sequence_parallel

    def forward(self, x, cache=None):
        x = _sp_constrain(x, self._sp)
        x = x + self.dropout(self.attn(self.ln_1(x), cache=cache))
        # kept, the backward's second forward skips out_proj and, under
        # tensor parallelism, the all-reduce behind it
        x = keep_name(x, "attn_res")
        x = x + self.mlp(self.ln_2(x))
        return x


class GPTModel(nn.Layer):
    """Decoder stack → final norm (reference: get_gpt_model.py GPTModel)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        if config.tensor_parallel:
            from ..distributed import fleet
            self.wte = fleet.VocabParallelEmbedding(config.vocab_size,
                                                    config.hidden_size)
        else:
            self.wte = nn.Embedding(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_seq_len, config.hidden_size)
        self.drop = nn.Dropout(config.dropout)
        self.h = nn.LayerList([GPTBlock(config)
                               for _ in range(config.num_layers)])
        norm = nn.RMSNorm if config.use_rms_norm else nn.LayerNorm
        self.ln_f = norm(config.hidden_size,
                         epsilon=config.layer_norm_epsilon)
        self._keep_plans = {}  # traced shape -> (keep sets, bytes, budget)

    def _keep_plan(self, x):
        """Which tagged tensors each block's backward keeps (one tuple of
        names a block), for a stack traced on the residual stream ``x``
        [B, S, H]: as many as fit what the device has free now, with the
        parameters and the optimizer's state in place, less
        ``step_reserve_bytes``. The traced shapes are global, so the bytes
        are cut to a device's share by the fleet mesh. Decided once a
        shape, so every trace of the step compiles the same program; each
        trace records it as a ``recompute.keep`` event."""
        cfg = self.config
        batch_div = model_deg = 1
        if cfg.tensor_parallel:
            from ..distributed.topology import get_hybrid_communicate_group
            from ..ops.pallas.flash_attention import flash_batch_axes
            mesh = get_hybrid_communicate_group().mesh
            model_deg = int(mesh.shape.get("model", 1))
            batch_div = math.prod(int(mesh.shape[a]) for a in
                                  flash_batch_axes(mesh, x.shape[0]))
        key = (tuple(x.shape), str(x.dtype), batch_div, model_deg)
        plan = self._keep_plans.get(key)
        if plan is None:
            tokens = x.shape[0] * x.shape[1] // batch_div
            itemsize = jnp.dtype(x.dtype).itemsize
            sizes = block_keep_bytes(cfg, tokens, itemsize, model_deg)
            free = device_free_bytes()
            budget = 0 if free is None else free - step_reserve_bytes(
                cfg, tokens, itemsize, model_deg)
            plan = (choose_keep(budget, sizes, cfg.num_layers), sizes,
                    budget)
            self._keep_plans[key] = plan
        tr = _trc.get_buffer()
        if tr is not None:
            keep, sizes, budget = plan
            blocks = {n: sum(n in k for k in keep) for n in sizes}
            tr.add("recompute.keep", time.time(), 0.0, cat="step", args={
                "names": [n for n in sizes if blocks[n]],
                "blocks": blocks, "layers": cfg.num_layers,
                "bytes": sum(sizes[n] * blocks[n] for n in sizes),
                "budget": int(budget), "name_bytes": sizes})
        return plan[0]

    def forward(self, input_ids, caches=None, pos_offset=0):
        b, s = input_ids.shape
        from .. import ops
        if isinstance(pos_offset, Tensor) and len(pos_offset.shape) == 2:
            # per-token absolute positions [B, S] (ragged serving round:
            # the flat token stream mixes rows at arbitrary offsets, so
            # positions arrive precomputed rather than as an arange)
            pos = pos_offset.astype("int64")
        elif isinstance(pos_offset, Tensor) and len(pos_offset.shape) == 1:
            # per-row offsets [B] (serving decode: ragged absolute
            # positions across the continuous batch)
            pos = pos_offset.astype("int64").unsqueeze(1) \
                + ops.arange(s, dtype="int64").unsqueeze(0)
        elif isinstance(pos_offset, Tensor):
            # traced offset (compiled decode): arange over the static
            # length, shifted by the traced cursor
            pos = (ops.arange(s, dtype="int64")
                   + pos_offset.astype("int64")).unsqueeze(0)
        else:
            pos = ops.arange(pos_offset, pos_offset + s,
                             dtype="int64").unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        remat = self.config.recompute and self.training and caches is None
        if remat:
            keep = self._keep_plan(x)
        for i, block in enumerate(self.h):
            if remat:
                # jax.checkpoint per block: backward rematerializes the
                # block (reference: fleet recompute granularity "full"),
                # less the matmul outputs the device has room to keep
                x = recompute(block, x, keep=keep[i])
            else:
                x = block(x, cache=None if caches is None else caches[i])
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    """LM head (weight-tied by default, reference parity: GPTForPretraining)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def cache_spec(self):
        """What each layer keeps for a cached token (the serving engine
        allocates its page pools from this): keys and values of
        ``[num_kv_heads, head_dim]``, in the weights' dtype."""
        from ..serving.kv_cache import kv_state
        cfg = self.config
        return [kv_state(cfg.num_heads, cfg.num_kv_heads,
                         cfg.hidden_size // cfg.num_heads,
                         self.gpt.wte.weight._data.dtype)
                ] * cfg.num_layers

    def forward(self, input_ids, caches=None, pos_offset=0):
        hidden = self.gpt(input_ids, caches=caches, pos_offset=pos_offset)
        if self.config.tie_word_embeddings:
            w = self.gpt.wte.weight  # [vocab, hidden]
            logits = apply("lm_head_tied",
                           lambda hs, wt: jnp.einsum("bsh,vh->bsv", hs, wt),
                           [hidden, w])
        else:
            logits = self.lm_head(hidden)
        return logits

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=None, eos_token_id=None, use_cache=True,
                 compiled=None):
        """Autoregressive decoding with a per-layer KV cache (reference
        capability: the generation loop over fused attention cache_kv /
        block_multihead_attention). Greedy when temperature == 0; otherwise
        temperature + optional top-k sampling from the framework RNG.

        compiled=True (auto for greedy decode): fixed-shape KV buffers +
        lax.while_loop — the whole decode loop is ONE XLA program (no
        per-token dispatch), output always [B, prompt+max_new_tokens]
        with eos padding. Sampling decode falls back to the eager loop
        (per-step RNG)."""
        from .. import ops
        from ..core import random as _random
        from ..core.autograd import no_grad

        if input_ids.shape[1] + max_new_tokens > self.config.max_seq_len:
            raise ValueError(
                f"prompt ({input_ids.shape[1]}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len "
                f"({self.config.max_seq_len}); positions past the table "
                "would silently clamp")
        if compiled is None:
            compiled = (temperature == 0.0 and use_cache)
        if compiled and temperature == 0.0 and use_cache:
            return self._generate_compiled(input_ids, max_new_tokens,
                                           eos_token_id)
        was_training = self.training
        self.eval()  # decode must be deterministic (dropout off) so the
        # cached and full-recompute paths agree
        try:
            with no_grad():
                caches = [{"k": None, "v": None}
                          for _ in self.gpt.h] if use_cache else None
                out_ids = input_ids
                logits = self(input_ids, caches=caches)
                cur_len = input_ids.shape[1]
                finished = None  # [B, 1] rows that already emitted eos
                for _ in range(max_new_tokens):
                    last = logits[:, -1]                   # [B, V]
                    if temperature == 0.0:
                        nxt = ops.argmax(last, axis=-1, keepdim=True)
                    else:
                        arr = last._data / np.float32(max(temperature,
                                                          1e-6))
                        if top_k is not None:
                            kth = jax.lax.top_k(arr, top_k)[0][..., -1:]
                            arr = jnp.where(arr < kth, -jnp.inf, arr)
                        nxt_arr = jax.random.categorical(
                            _random.next_key(), arr, axis=-1)[:, None]
                        from ..core.tensor import Tensor
                        nxt = Tensor(nxt_arr, stop_gradient=True)
                    nxt = nxt.astype(input_ids.dtype)
                    if eos_token_id is not None:
                        from ..core.tensor import Tensor
                        is_eos = nxt._data == eos_token_id
                        if finished is None:
                            finished = is_eos
                        else:
                            # frozen rows keep emitting eos padding
                            nxt = Tensor(jnp.where(
                                finished, jnp.asarray(
                                    eos_token_id, nxt._data.dtype),
                                nxt._data), stop_gradient=True)
                            finished = finished | is_eos
                    out_ids = ops.concat([out_ids, nxt], axis=1)
                    if finished is not None and bool(
                            jnp.all(finished)):
                        break
                    if use_cache:
                        logits = self(nxt, caches=caches,
                                      pos_offset=cur_len)
                    else:
                        logits = self(out_ids)
                    cur_len += 1
                return out_ids
        finally:
            if was_training:
                self.train()

    def _generate_compiled(self, input_ids, max_new_tokens, eos_token_id):
        """Greedy decode as ONE XLA while program (VERDICT r3 item 3):
        prefill fills fixed [B, total, H, Dh] KV buffers, then
        paddle.while_loop (lax.while_loop) carries (ids, next token,
        cursor, finished, caches) — every step one fused in-program
        forward, early-exiting when all rows hit eos."""
        from .. import ops
        from ..core.autograd import no_grad
        from ..jit.control_flow import while_loop

        B, prompt = input_ids.shape
        total = prompt + max_new_tokens
        cfg = self.config
        Hh = cfg.num_kv_heads   # cache buffers hold KV heads (GQA-sized)
        Dh = cfg.hidden_size // cfg.num_heads
        dt = self.gpt.wte.weight._data.dtype
        eos = -1 if eos_token_id is None else int(eos_token_id)
        was_training = self.training
        self.eval()
        try:
            with no_grad():
                caches = [{"static": True,
                           "k": Tensor(jnp.zeros((B, total, Hh, Dh), dt)),
                           "v": Tensor(jnp.zeros((B, total, Hh, Dh), dt)),
                           "len": Tensor(jnp.asarray(0, jnp.int32))}
                          for _ in self.gpt.h]
                logits = self(input_ids, caches=caches)      # prefill
                nxt = ops.argmax(logits[:, -1], axis=-1,
                                 keepdim=True).astype(input_ids.dtype)
                finished = nxt.equal(
                    Tensor(jnp.asarray(eos, nxt._data.dtype)))
                ids_buf = ops.concat(
                    [input_ids,
                     Tensor(jnp.zeros((B, max_new_tokens),
                                      input_ids._data.dtype))], axis=1)
                ids_buf = _ids_write(ids_buf, nxt,
                                     Tensor(jnp.asarray(prompt, jnp.int32)))
                cur = Tensor(jnp.asarray(prompt + 1, jnp.int32))
                total_t = Tensor(jnp.asarray(total, jnp.int32))
                n_rows = Tensor(jnp.asarray(B, jnp.int32))

                def cond_fn(ids_buf, nxt, cur, finished, caches):
                    more = cur < total_t
                    if eos_token_id is not None:
                        alive = finished.astype("int32").sum() < n_rows
                        more = more.logical_and(alive)
                    return more

                def body_fn(ids_buf, nxt, cur, finished, caches):
                    logits = self(nxt, caches=caches,
                                  pos_offset=caches[0]["len"])
                    new = ops.argmax(logits[:, -1], axis=-1,
                                     keepdim=True).astype(ids_buf.dtype)
                    if eos_token_id is not None:
                        eos_t = Tensor(jnp.asarray(eos, new._data.dtype))
                        new = Tensor(jnp.where(finished._data,
                                               eos_t._data, new._data),
                                     stop_gradient=True)
                        finished = finished.logical_or(new.equal(eos_t))
                    ids_buf = _ids_write(ids_buf, new, cur)
                    one = Tensor(jnp.asarray(1, jnp.int32))
                    return [ids_buf, new, cur + one, finished, caches]

                out = while_loop(cond_fn, body_fn,
                                 [ids_buf, nxt, cur, finished, caches])
                return out[0]
        finally:
            if was_training:
                self.train()


class GPTPretrainingCriterion(nn.Layer):
    """Masked LM loss (reference: gpt pretraining criterion; uses
    ParallelCrossEntropy under mp)."""

    def __init__(self, config: GPTConfig = None):
        super().__init__()
        self._tp = bool(config and config.tensor_parallel)
        if self._tp:
            from ..distributed import fleet
            self.pce = fleet.ParallelCrossEntropy()

    def forward(self, logits, labels, loss_mask=None):
        b, s, v = logits.shape
        flat_logits = logits.reshape([b * s, v])
        flat_labels = labels.reshape([b * s])
        if self._tp:
            losses = self.pce(flat_logits, flat_labels)
        else:
            losses = F.cross_entropy(flat_logits, flat_labels,
                                     reduction="none")
        if loss_mask is not None:
            m = loss_mask.reshape([b * s]).astype("float32")
            return (losses * m).sum() / m.sum()
        return losses.mean()


class _EmbeddingPipe(nn.Layer):
    """Stage-0 pipeline block: token + position embedding."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_seq_len, config.hidden_size)
        self.drop = nn.Dropout(config.dropout)

    def forward(self, input_ids):
        from .. import ops
        s = input_ids.shape[1]
        pos = ops.arange(0, s, dtype="int64").unsqueeze(0)
        return self.drop(self.wte(input_ids) + self.wpe(pos))


class _LMHeadPipe(nn.Layer):
    """Last pipeline block: final norm + untied LM head."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        norm = nn.RMSNorm if config.use_rms_norm else nn.LayerNorm
        self.ln_f = norm(config.hidden_size,
                         epsilon=config.layer_norm_epsilon)
        self.head = nn.Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)

    def forward(self, x):
        return self.head(self.ln_f(x))


def GPTForCausalLMPipe(config: GPTConfig, num_stages=None, loss_fn=None):
    """Pipeline-parallel GPT built from LayerDescs (reference: the fleet
    GPTForPretrainingPipe recipe over PipelineLayer, pp_layers.py:237)."""
    from ..distributed.fleet import LayerDesc, PipelineLayer
    descs = [LayerDesc(_EmbeddingPipe, config)]
    descs += [LayerDesc(GPTBlock, config) for _ in range(config.num_layers)]
    descs.append(LayerDesc(_LMHeadPipe, config))
    if loss_fn is None:
        crit = GPTPretrainingCriterion(config)

        def loss_fn(logits, labels):
            return crit(logits, labels)
    return PipelineLayer(layers=descs, num_stages=num_stages,
                         loss_fn=loss_fn)
