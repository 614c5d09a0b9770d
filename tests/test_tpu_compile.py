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
