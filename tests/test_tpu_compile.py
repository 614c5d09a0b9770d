"""The main path's Pallas kernels compile for a TPU v5e that is described,
not attached — at gpt_1p3b widths (hidden 2048, 16 heads x 128, ffn 8192,
vocab 50304), the sizes ``chip_smoke.py`` runs on the chip.

Interpret mode, which every other kernel test uses, cannot show what the
chip's compiler refuses: a slice off the tiling, too much VMEM for one
block. These compiles can, in about two seconds each and with no chip.
A compile that passes is not a chip run and says nothing about results
or speed.

The topology is described inside a module-scoped fixture and nowhere
else: the process that describes it loads libtpu and holds its lock until
it exits, so no import, ``skipif`` or ``parametrize`` argument may do it —
under several xdist workers every worker imports this file, and only the
one that is handed it may load the library. For the same reason these
tests stay in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, HEADS, DH, FFN, VOCAB = 2048, 16, 128, 8192, 50304
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for an absent chip is written to the
    # persistent cache but cannot be read back without one: keep these
    # compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip from shapes alone; -> the
    optimized HLO text, which must hold the Mosaic kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# [B, S, H, D] attention operands under the schedule ``block_schedule``
# picks: the one-chip cell's call (one 2048 x 2048 step a head), the
# four-chip cell's share of a device, two longer sequences for the VMEM
# budget (2048 x 2048 blocks, several a head), a sequence that needs
# padding, and gpt_small's 12 x 64 heads
FLASH_SHAPES = [
    (2, 2048, HEADS, DH), (4, 2048, 20, DH), (1, 4096, HEADS, DH),
    (1, 8192, HEADS, DH), (2, 1024, HEADS, DH), (2, 1000, HEADS, DH),
    (2, 1024, 12, 64)]


@pytest.mark.parametrize("b,s,heads,dh", FLASH_SHAPES)
def test_flash_forward(one_chip, b, s, heads, dh):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    qkv = ((b, s, heads, dh), BF16)
    _compile(lambda q, k, v: flash_attention_bshd(q, k, v, causal=True),
             one_chip, qkv, qkv, qkv)


@pytest.mark.parametrize("b,s,heads,dh", FLASH_SHAPES)
def test_flash_backward(one_chip, b, s, heads, dh):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    qkv = ((b, s, heads, dh), BF16)

    def loss(q, k, v):
        return flash_attention_bshd(q, k, v, causal=True) \
            .astype(F32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    qkv, qkv, qkv)
    # forward, dq and dk/dv are three kernels
    assert text.count("tpu_custom_call") >= 3


# what else reaches the kernel through F.scaled_dot_product_attention:
# float32 operands (twice the block bytes), no causal mask, fewer queries
# than keys, a sequence shorter than one block
@pytest.mark.parametrize("shape,sk,dtype,causal", [
    ((2, 2048, HEADS, DH), None, F32, True),
    ((2, 2048, HEADS, DH), None, BF16, False),
    ((2, 640, 12, 64), 1408, BF16, False),
    ((2, 100, 4, 32), None, BF16, True)])
def test_flash_backward_other_callers(one_chip, shape, sk, dtype, causal):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    b, s, heads, dh = shape
    q, kv = (shape, dtype), ((b, sk or s, heads, dh), dtype)

    def loss(q, k, v):
        return flash_attention_bshd(q, k, v, causal=causal) \
            .astype(F32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)
    assert text.count("tpu_custom_call") >= 3


def _kernel_names(text):
    """Instruction names of the compiled text's Mosaic kernels, without
    the number XLA appends: ``%flash_attention_fwd.3 = ...`` ->
    ``flash_attention_fwd``."""
    import re
    return sorted(re.match(r"\s*(?:ROOT\s+)?%?([^\s=]+?)(?:\.\d+)*\s*=",
                           line).group(1)
                  for line in text.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in line)


FLASH = ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
         "flash_attention_fwd"]


def _flash_loss(attend):
    return lambda q, k, v: jnp.sin(attend(q, k, v).astype(F32)).sum()


def _tape_step(attend):
    """Forward and backward as the program's tape runs them inside the
    whole step: the forward at dispatch, ``jax.vjp`` materialised only at
    backward (``core/autograd.py``)."""
    def step(q, k, v):
        out = attend(q, k, v)
        _, pull = jax.vjp(attend, q, k, v)
        return out, pull(jnp.cos(out.astype(F32)).astype(out.dtype))
    return step


# the kernels' names are a contract with the benchmark: a device trace
# names an op by its HLO instruction, and ``pallas_call(name=...)`` must
# reach it through whatever wraps the call on the training path
def test_flash_kernels_keep_their_names(one_chip):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    qkv = ((2, 2048, HEADS, DH), BF16)

    def attend(q, k, v):
        return flash_attention_bshd(q, k, v, causal=True)

    assert _kernel_names(_compile(attend, one_chip, qkv, qkv, qkv)) == \
        ["flash_attention_fwd"]
    # differentiated directly, jax wraps each name in its transforms'
    # (``jvp_flash_attention_fwd_``): the kernel's own name is still there
    names = _kernel_names(_compile(
        jax.grad(_flash_loss(attend), argnums=(0, 1, 2)), one_chip,
        qkv, qkv, qkv))
    assert len(names) == 3
    assert sorted(k for n in names for k in FLASH if k in n) == FLASH


def test_flash_kernels_keep_their_names_under_checkpoint(one_chip):
    """As the training step runs them: per-block recompute is
    ``jax.checkpoint``, whose forward and rematerialised forward both
    carry the kernel's name and not ``checkpoint`` or
    ``rematted_computation``."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    qkv = ((2, 2048, HEADS, DH), BF16)
    attend = jax.checkpoint(
        lambda q, k, v: flash_attention_bshd(q, k, v, causal=True))
    names = _kernel_names(_compile(_tape_step(attend), one_chip,
                                   qkv, qkv, qkv))
    assert sorted(set(names)) == FLASH
    assert len(names) == 4 and names.count("flash_attention_fwd") == 2


def test_flash_kernels_keep_their_names_under_shard_map(topo):
    """``sharded_flash_attention`` (heads over 'model', batch over 'data')
    inside ``jax.checkpoint``, compiled for four described chips: the
    hybrid step's layout."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.ops.pallas.flash_attention import \
        sharded_flash_attention
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2),
                ("data", "model"))
    fa = jax.checkpoint(sharded_flash_attention(mesh, causal=True))
    qkv = jax.ShapeDtypeStruct(
        (4, 2048, HEADS, DH), BF16,
        sharding=NamedSharding(mesh, P("data", None, "model", None)))

    text = jax.jit(_tape_step(fa)).lower(qkv, qkv, qkv).compile().as_text()
    names = _kernel_names(text)
    assert sorted(set(names)) == FLASH
    assert len(names) == 4 and names.count("flash_attention_fwd") == 2


def _collectives(text, kind):
    """The compiled text's instructions of one collective kind, started
    or whole: ``all-gather(`` and ``all-gather-start(``."""
    return [line.strip() for line in text.splitlines()
            if f" {kind}(" in line or f" {kind}-start(" in line]


def test_sharded_flash_moves_nothing_between_chips(topo):
    """The hybrid step's mesh, 'sharding' 2 x 'model' 2, with the batch
    split over 'sharding' as ``shard_batch`` leaves it: the shard_map names
    that axis too, so forward, rematerialised forward and backward compile
    with no collective, and each chip's kernel attends its own 2 of the 4
    sequences and 20 of the 40 heads."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.ops.pallas.flash_attention import \
        sharded_flash_attention
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2),
                ("sharding", "model"))
    fa = jax.checkpoint(sharded_flash_attention(mesh, causal=True))
    qkv = jax.ShapeDtypeStruct(
        (4, 2048, 40, DH), BF16,
        sharding=NamedSharding(mesh, P("sharding", None, "model", None)))

    text = jax.jit(_tape_step(fa)).lower(qkv, qkv, qkv).compile().as_text()
    names = _kernel_names(text)
    assert sorted(set(names)) == FLASH and len(names) == 4
    for kind in ("all-gather", "all-reduce", "collective-permute",
                 "all-to-all"):
        assert _collectives(text, kind) == []
    # [B, S, H, D] -> [BH, S, D] inside the shard_map: 2 x 20 on a chip
    assert "bf16[40,2048,128]" in text and "bf16[80,2048,128]" not in text


def _fleet_mesh(topo, monkeypatch):
    """fleet's own mesh, mp 2 x sharding 2, over the four described chips.
    fleet builds its mesh from jax.devices(): hand it the described chips
    (which also makes on_tpu() say yes, so the kernel is chosen as on the
    chip), and leave the weights where they were drawn -- nothing can be
    put on a chip that is not there."""
    from paddle_tpu.distributed import (collective, env, fleet, placement,
                                        topology)
    chips = list(topo.devices[:4])
    monkeypatch.setattr(jax, "devices", lambda *a: chips)
    monkeypatch.setattr(jax, "device_count", lambda *a: len(chips))
    monkeypatch.setattr(placement, "place_global", lambda arr, s: arr)
    # fleet.init also builds the world mesh and, where this worker has
    # none yet, the default process group from the same devices: put all
    # of it back, or the next file's eager collectives on this worker try
    # to place arrays on chips that are not there
    for mod, name in ((topology, "_hcg"), (fleet, "_strategy"),
                      (fleet, "_fleet_initialized"), (env, "_world_mesh"),
                      (env, "_initialized"),
                      (collective, "_default_group")):
        monkeypatch.setattr(mod, name, getattr(mod, name))  # put back after
    monkeypatch.setattr(collective, "_groups", dict(collective._groups))
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 1,
                               "sharding_degree": 2, "mp_degree": 2}
    mesh = fleet.init(is_collective=True, strategy=strategy).mesh
    assert dict(mesh.shape) == {"data": 1, "pipe": 1, "sharding": 2,
                                "sep": 1, "model": 2}
    return mesh


def test_gpt_attention_gathers_no_qkv_under_the_fleet_mesh(topo,
                                                           monkeypatch):
    """One ``GPTAttention`` at GPT-3 13B widths under fleet's own mesh
    (mp 2 x sharding 2 over the four described chips), forward and
    backward, the input split over 'sharding': no all-gather puts q, k,
    v, the output or a cotangent of theirs together across the sharding
    group, and the kernels run on a chip's own share."""
    import paddle_tpu as paddle
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.fleet.pipeline_compiled import \
        _functionalize
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt import GPTAttention

    mesh = _fleet_mesh(topo, monkeypatch)
    hidden, heads, batch, seq = 5120, 40, 4, 2048
    paddle.seed(0)
    attn = GPTAttention(GPTConfig(
        vocab_size=128, hidden_size=hidden, num_layers=1, num_heads=heads,
        max_seq_len=seq, dropout=0.0, tensor_parallel=True))
    fn, params = _functionalize(attn)
    specs = {"qkv_proj.weight": P(None, "model"),
             "qkv_proj.bias": P("model"),
             "out_proj.weight": P("model", None), "out_proj.bias": P()}
    names = [n for n, _ in attn.named_parameters()]
    assert sorted(names) == sorted(specs)

    def aval(shape, spec):
        return jax.ShapeDtypeStruct(tuple(shape), BF16,
                                    sharding=NamedSharding(mesh, spec))

    def loss(arrs, x):
        return jnp.sin(fn(arrs, x).astype(F32)).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        [aval(p.shape, specs[n]) for n, p in zip(names, params)],
        aval((batch, seq, hidden), P("sharding", None, None))
    ).compile().as_text()
    assert sorted(set(k for n in _kernel_names(text)
                      for k in FLASH if k in n)) == FLASH
    # on a chip: 2 sequences x 20 heads, never the sharding group's 4
    assert "bf16[40,2048,128]" in text and "bf16[80,2048,128]" not in text
    gathered = [line for line in _collectives(text, "all-gather")
                if f"[{batch},{seq}," in line.split(" all-gather")[0]]
    assert gathered == []


@pytest.mark.parametrize("keep,reduced,forwards", [
    ((), 5, 2), (("attn_res",), 4, 2),
    (("attn_res", "fc1", "attn_out"), 4, 1)])
def test_kept_residual_drops_one_tensor_parallel_all_reduce(
        topo, monkeypatch, keep, reduced, forwards):
    """One ``GPTBlock`` under mp 2 x sharding 2 through
    ``recompute(block, x, keep=...)`` and the program's tape: the
    [B, S, H] all-reduces over the 'model' pairs, counted by
    ``channel_id``, are two in the forward, two in the backward and one
    in the forward that ``jax.checkpoint`` runs again; keeping
    ``attn_res`` takes that one (behind ``out_proj``) out. The flash
    forward kernel runs twice unless ``attn_out`` (tagged in its own
    forward rule) is kept."""
    import re
    import paddle_tpu as paddle
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt import GPTBlock

    mesh = _fleet_mesh(topo, monkeypatch)
    hidden, heads, batch, seq = 1024, 8, 4, 512
    paddle.seed(0)
    block = GPTBlock(GPTConfig(
        vocab_size=128, hidden_size=hidden, num_layers=1, num_heads=heads,
        max_seq_len=seq, dropout=0.0, tensor_parallel=True))
    named = list(block.named_parameters())
    params = [p for _, p in named]
    split = {"attn.qkv_proj.weight": P(None, "model"),
             "attn.qkv_proj.bias": P("model"),
             "attn.out_proj.weight": P("model", None),
             "mlp.fc1.weight": P(None, "model"),
             "mlp.fc1.bias": P("model"),
             "mlp.fc2.weight": P("model", None)}
    assert set(split) < {n for n, _ in named}

    def grads(arrs, x):
        saved = [p._data for p in params]
        for p, a in zip(params, arrs):
            p._data = a
        try:
            out = fleet.recompute(block, paddle.to_tensor(x), keep=keep)
            out.astype("float32").sin().sum().backward()
            return [p.grad._data for p in params]
        finally:
            for p, a in zip(params, saved):
                p._data, p._grad = a, None

    def aval(shape, spec):
        return jax.ShapeDtypeStruct(tuple(shape), BF16,
                                    sharding=NamedSharding(mesh, spec))

    text = jax.jit(grads).lower(
        [aval(p.shape, split.get(n, P())) for n, p in named],
        aval((batch, seq, hidden), P("sharding", None, None))
    ).compile().as_text()
    on_chip = f"bf16[{batch // 2},{seq},{hidden}]"
    channels = {re.search(r"channel_id=(\d+)", line).group(1)
                for line in _collectives(text, "all-reduce")
                if line.split(" all-reduce")[0].count(on_chip)
                # the 'model' pairs {0,1},{2,3}; the gradients' reductions
                # over the 'sharding' pairs read [2,2]<=[2,2]T(1,0)
                and "replica_groups=[2,2]<=[4]," in line}
    assert len(channels) == reduced
    assert _kernel_names(text).count("flash_attention_fwd") == forwards


def test_layer_norm(one_chip):
    from paddle_tpu.ops.pallas.layer_norm import layer_norm
    _compile(lambda x, w, b: layer_norm(x, w, b), one_chip,
             ((4096, H), BF16), ((H,), BF16), ((H,), BF16))


def test_rms_norm(one_chip):
    from paddle_tpu.ops.pallas.rms_norm import rms_norm
    _compile(lambda x, w: rms_norm(x, w), one_chip,
             ((4096, H), BF16), ((H,), BF16))


# the MLP weight and the embedding table, f32 masters as AdamW holds them
@pytest.mark.parametrize("shape", [(H, FFN), (VOCAB, H)])
def test_fused_adamw(one_chip, shape):
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
    t = (shape, F32)
    _compile(lambda w, g, m, v: fused_adamw(
        w, g, m, v, 2e-4, 0.9, 0.999, 1e-8, 0.01, 10.0, 1000.0),
        one_chip, t, t, t, t)


# serving pools [pages, page, KVH, D] and 2048 tokens of context a row
POOL = ((512, 16, HEADS, DH), BF16)
MAX_PAGES = 128


def test_paged_decode_attention(one_chip):
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    b = 8
    _compile(lambda q, k, v, bt, lens: paged_attention(q, k, v, bt, lens),
             one_chip, ((b, HEADS, DH), BF16), POOL, POOL,
             ((b, MAX_PAGES), jnp.int32), ((b,), jnp.int32))


# the round sizes of the smoke's engine (8 slots + a 64-token chunk pads
# to 128) and a larger one
@pytest.mark.parametrize("tokens", [128, 256])
def test_ragged_paged_attention(one_chip, tokens):
    from paddle_tpu.ops.pallas.ragged_attention import \
        ragged_paged_attention
    rows = 8
    row = ((rows,), jnp.int32)
    text = _compile(lambda q, k, v, rs, rl, kl, bt: ragged_paged_attention(
        q, k, v, rs, rl, kl, bt), one_chip,
        ((tokens, HEADS, DH), BF16), POOL, POOL, row, row, row,
        ((rows, MAX_PAGES), jnp.int32))
    assert _kernel_names(text) == ["ragged_paged_attention"]


def test_ragged_kernel_keeps_its_name_under_checkpoint(one_chip):
    from paddle_tpu.ops.pallas.ragged_attention import \
        ragged_paged_attention
    rows = 8
    row = ((rows,), jnp.int32)
    text = _compile(jax.checkpoint(ragged_paged_attention), one_chip,
                    ((128, HEADS, DH), BF16), POOL, POOL, row, row, row,
                    ((rows, MAX_PAGES), jnp.int32))
    assert _kernel_names(text) == ["ragged_paged_attention"]


def test_xla_reference_attention_stays_in_f32(one_chip):
    """The XLA attention that stands in where flash is not eligible must
    not widen to f64 under jax_enable_x64 (a numpy float64 scale did):
    the TPU emulates f64, and one [2, 16, 2048, 2048] layer of it took
    15 GiB by this compiler's count."""
    import paddle_tpu  # noqa: F401  (turns jax_enable_x64 on)
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn import functional as F

    def attend(q, k, v):
        # flash is never eligible here: jax.devices() is the CPU
        return F.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(v), is_causal=True)._data

    qkv = jax.ShapeDtypeStruct((2, 2048, HEADS, DH), BF16,
                               sharding=one_chip)
    compiled = jax.jit(attend).lower(qkv, qkv, qkv).compile()
    assert "f64[" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30


# ------------------------------------------------------------------------
# The latent-attention / dropless-expert decoder at Kimi-K2's published
# widths: hidden 7168, 64 heads over one 576-wide latent row (stored in
# 640 lanes), 2048-wide experts, 12 of 384 held.
K2 = dict(hidden=7168, heads=64, latent=576, lanes=640, value=512,
          expert=2048, held=12)


def _named(text, kernel):
    return [n for n in _kernel_names(text) if kernel in n]


@pytest.mark.parametrize("tokens,page", [(32, 256), (544, 256), (32, 64)])
def test_mla_ragged_attention_at_published_widths(one_chip, tokens, page):
    """A decode round (one item a token) and the largest mixed round
    (items of 16 tokens), over a pool of 393,216 tokens."""
    from paddle_tpu.ops.pallas.mla_ragged_attention import \
        mla_ragged_attention
    rows, pages = 32, 393216 // page
    meta = ((rows,), jnp.int32)
    text = _compile(
        lambda q, pool, rs, rl, kl, bt: mla_ragged_attention(
            q, pool, rs, rl, kl, bt, scale=0.1447, value_width=K2["value"]),
        one_chip, ((tokens, K2["heads"], K2["latent"]), BF16),
        ((pages, page, K2["lanes"]), BF16), meta, meta, meta,
        ((rows, 16384 // page), jnp.int32))
    assert _named(text, "mla_ragged_attention") == \
        ["mla_ragged_attention"]


@pytest.mark.parametrize("rows,tile_m,k,n", [
    (640, 32, 7168, 4096),        # a decode round's gate and up products
    (640, 32, 2048, 7168),        # ... and its down product
    (7424, 256, 7168, 4096),      # 544 tokens x 8 + 12 tiles of slack
])
def test_moe_grouped_matmul_at_published_widths(one_chip, rows, tile_m, k,
                                                n):
    from paddle_tpu.ops.pallas.moe_grouped_matmul import moe_grouped_matmul
    text = _compile(
        lambda x, w, te, nt: moe_grouped_matmul(x, w, te, nt, tile_m),
        one_chip, ((rows, k), BF16), ((K2["held"], k, n), BF16),
        ((rows // tile_m,), jnp.int32), ((1,), jnp.int32))
    assert _named(text, "moe_grouped_matmul") == \
        ["moe_grouped_matmul"]


def test_mla_moe_round_program_at_published_widths(one_chip, monkeypatch):
    """One dense and one expert layer of the decoder behind the serving
    engine's ragged round, every width as published: the program holds
    the latent kernel once a layer and the grouped product twice an
    expert layer, under their names."""
    import json
    import os
    from paddle_tpu.models import MLAMoEForCausalLM
    from paddle_tpu.nn import initializer as init
    from paddle_tpu.ops.pallas import _common as gate
    from paddle_tpu.serving import ServingEngine
    from benchmark.models import mla_moe as family
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "kimi-k2p6-serve.json")) as f:
        cfg = json.load(f)
    cfg["num_layers"] = 2
    init.set_global_initializer(init.Constant(0.01), init.Constant(0.0))
    try:
        model = MLAMoEForCausalLM(family.model_config(
            cfg, moe_backend="pallas"))
    finally:
        init.set_global_initializer(None, None)
    eng = ServingEngine(model, page_size=256, num_pages=8, max_slots=32,
                        prefill_chunk=512, attn_backend="pallas",
                        token_pads=[32, 544])
    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    eng._jit = False
    step = jax.jit(eng._build_ragged_step(), donate_argnums=(6,))

    def aval(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    text = step.lower([aval(a) for a in eng._param_arrays], i32(544),
                      i32(32), i32(32), i32(32), i32(32, eng.max_pages),
                      jax.tree_util.tree_map(aval, eng.kv.pools)
                      ).compile().as_text()
    assert len(_named(text, "mla_ragged_attention")) == 2
    assert len(_named(text, "moe_grouped_matmul")) == 2


# ------------------------------------------------------------------------
# The gated window / full attention decoder at Trinity-Large's published
# widths: hidden 3072, 48 query heads over 8 KV heads of 128, a window of
# 4,096 tokens, 3072-wide experts, 32 of 256 held; pages of 256 tokens,
# the full group's 2,305 and the window group's 641.
@pytest.mark.parametrize("tokens,pages,window", [
    (32, 2305, None), (544, 2305, None), (32, 641, 4096), (544, 641, 4096)])
def test_windowed_ragged_attention_at_published_widths(one_chip, tokens,
                                                       pages, window):
    """A decode round (items of 8 tokens, 16 query rows a KV head at work)
    and the largest mixed round (items of 32 tokens), with a window and
    without."""
    from paddle_tpu.ops.pallas.windowed_ragged_attention import \
        windowed_ragged_attention
    meta = ((32,), jnp.int32)
    pool = ((pages, 256, 8 * 128), BF16)
    text = _compile(
        lambda q, k, v, rs, rl, kl, bt: windowed_ragged_attention(
            q, k, v, rs, rl, kl, bt, window=window),
        one_chip, ((tokens, 48, 128), BF16), pool, pool, meta, meta, meta,
        ((32, 18432 // 256), jnp.int32))
    assert _named(text, "windowed_ragged_attention") == \
        ["windowed_ragged_attention"]


def test_afmoe_round_program_at_published_widths(one_chip, monkeypatch):
    """A dense sliding layer, a sliding and a full expert layer of the
    decoder behind the serving engine's ragged round, every width as
    published and one block table a page group: the program holds the
    windowed kernel once a layer and the grouped product twice an expert
    layer, under their names, and fits the chip with its pools."""
    import json
    import os
    from paddle_tpu.models import AfmoeForCausalLM
    from paddle_tpu.nn import initializer as init
    from paddle_tpu.ops.pallas import _common as gate
    from paddle_tpu.serving import ServingEngine
    from benchmark.models import afmoe as family
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "trinity-large-serve.json")) as f:
        cfg = json.load(f)
    cfg.update(num_layers=3, layers_run=[0, 10, 11],
               layer_types_run=["sliding_attention"] * 2
               + ["full_attention"])
    init.set_global_initializer(init.Constant(0.01), init.Constant(0.0))
    try:
        model = AfmoeForCausalLM(family.model_config(
            cfg, moe_backend="pallas"))
    finally:
        init.set_global_initializer(None, None)
    eng = ServingEngine(model, page_size=256, max_slots=32,
                        num_pages={"kv_windowed.w4096": 8, "kv_windowed": 4},
                        prefill_chunk=512, attn_backend="pallas",
                        prefix_cache=False, token_pads=[32, 544])
    assert [(g.name, g.layers) for g in eng.kv.groups] == [
        ("kv_windowed.w4096", [0, 1]), ("kv_windowed", [2])]
    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    eng._jit = False
    step = jax.jit(eng._build_ragged_step(), donate_argnums=(6,))

    def aval(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    assert eng._bt_shape() == (2, 32, 72)
    text = step.lower([aval(a) for a in eng._param_arrays], i32(544),
                      i32(32), i32(32), i32(32), i32(*eng._bt_shape()),
                      jax.tree_util.tree_map(aval, eng.kv.pools)
                      ).compile().as_text()
    assert len(_named(text, "windowed_ragged_attention")) == 3
    assert len(_named(text, "moe_grouped_matmul")) == 4


# ------------------------------------------------------------------------
# The delta-rule / latent attention decoder at Ling-3.0-flash's published
# widths: hidden 2560, 32 heads of 128 (a 128 x 128 float32 state a head
# and request), latent 512 + 64, 768-wide experts, 32 of 512 held in 8
# groups; 128 slots, pages of 256 tokens.
@pytest.mark.parametrize("tokens", [128, 640, 1152])
def test_kda_ragged_at_published_widths(one_chip, tokens):
    """A decode round and the mixed rounds with one and two 512-token
    chunks over 128 slots and the scrap slot: whatever the launch's shape,
    the token form for the rows of one token (one item a row) and the
    chunked form in blocks of 64 through the matrix unit for the others
    (no block in a decode round): two launches over the one pool, updated
    in place, each under a name that holds ``kda_ragged`` (the benchmark's
    readers sum the events that do)."""
    from paddle_tpu.ops.pallas.kda_ragged import kda_ragged
    tok, meta = ((tokens, 32, 128), F32), ((128,), jnp.int32)
    pool = ((129, 32, 128, 128), F32)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (tok, tok, tok, tok, ((tokens, 32), F32), pool,
                         meta, meta, meta, meta)]
    c = jax.jit(kda_ragged, donate_argnums=(5,)).lower(*args).compile()
    assert _named(c.as_text(), "kda_ragged") == \
        ["kda_ragged_chunks", "kda_ragged_tokens"]
    assert c.memory_analysis().alias_size_in_bytes >= 129 * 32 * 128 * 128 * 4


def test_kda_mla_moe_round_program_at_published_widths(one_chip,
                                                       monkeypatch):
    """The dense KDA layer, an expert KDA layer and the expert MLA layer
    of the decoder behind the serving engine's ragged round at its
    largest (128 decode rows and two 512-token chunks), every width as
    published, the plan as the one message with each row's slot: the
    program holds the recurrence's two forms once each a KDA layer, the
    latent kernel once and the grouped product twice an expert layer,
    under their names, and updates the state pools in place."""
    import json
    import os
    from paddle_tpu.models import KDAMLAMoEForCausalLM
    from paddle_tpu.nn import initializer as init
    from paddle_tpu.ops.pallas import _common as gate
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.engine import _message_len
    from benchmark.models import kda_mla_moe as family
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "ling-3p0-flash-serve.json")) as f:
        cfg = json.load(f)
    cfg.update(num_layers=3, layers_run=[1, 10, 11],
               layer_types_run=["kda", "kda", "mla"])
    init.set_global_initializer(init.Constant(0.01), init.Constant(0.0))
    try:
        model = KDAMLAMoEForCausalLM(family.model_config(
            cfg, moe_backend="pallas"))
    finally:
        init.set_global_initializer(None, None)
    eng = ServingEngine(model, page_size=256, num_pages=8, max_slots=128,
                        prefill_chunk=512, prefill_token_budget=1024,
                        attn_backend="pallas", prefix_cache=False,
                        token_pads=[128, 1152])
    assert eng.kv.state_layers == [0, 1] and eng.state_backend == "pallas"
    assert eng.kv.pools[0]["state"].shape == (129, 32, 128, 128)
    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    eng._jit = False
    step = jax.jit(eng._build_round(), donate_argnums=(2,))

    def aval(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    n = _message_len(1152, 128, eng._bt_shape(), True)
    c = step.lower([aval(a) for a in eng._param_arrays],
                   jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
                   jax.tree_util.tree_map(aval, eng.kv.pools)).compile()
    text = c.as_text()
    # a KDA layer's rows of one token and its chunk rows: two launches
    assert _named(text, "kda_ragged") == \
        ["kda_ragged_chunks"] * 2 + ["kda_ragged_tokens"] * 2
    assert len(_named(text, "mla_ragged_attention")) == 1
    assert len(_named(text, "moe_grouped_matmul")) == 4
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= eng.kv.nbytes()
