"""Pallas flash-attention kernel vs reference attention (interpret mode on
the CPU mesh; the same kernel compiles for TPU via Mosaic).

Reference precedent: test/legacy_test/test_flash_attention.py compares
flash_attn against a plain-softmax implementation.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd


def _ref_attention(q, k, v, causal):
    b, s, h, d = q.shape
    qf = jnp.swapaxes(q.astype(jnp.float32), 1, 2)
    kf = jnp.swapaxes(k.astype(jnp.float32), 1, 2)
    vf = jnp.swapaxes(v.astype(jnp.float32), 1, 2)
    scores = jnp.einsum("bhsd,bhtd->bhst", qf, kf) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((s, k.shape[1]), bool))
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vf)
    return jnp.swapaxes(out, 1, 2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(1, 128, 2, 64), (2, 256, 2, 32)])
def test_flash_forward_matches_reference(causal, shape):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(*shape), jnp.float32)
    k = jnp.asarray(rng.randn(*shape), jnp.float32)
    v = jnp.asarray(rng.randn(*shape), jnp.float32)
    out = flash_attention_bshd(q, k, v, causal=causal, interpret=True)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_flash_forward_unaligned_seq_causal():
    rng = np.random.RandomState(1)
    shape = (1, 100, 2, 32)  # S not a multiple of the block: padded path
    q = jnp.asarray(rng.randn(*shape), jnp.float32)
    k = jnp.asarray(rng.randn(*shape), jnp.float32)
    v = jnp.asarray(rng.randn(*shape), jnp.float32)
    out = flash_attention_bshd(q, k, v, causal=True, interpret=True)
    ref = _ref_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_reference(causal):
    rng = np.random.RandomState(2)
    shape = (1, 128, 2, 32)
    q = jnp.asarray(rng.randn(*shape), jnp.float32)
    k = jnp.asarray(rng.randn(*shape), jnp.float32)
    v = jnp.asarray(rng.randn(*shape), jnp.float32)
    g = jnp.asarray(rng.randn(*shape), jnp.float32)

    def flash_loss(q, k, v):
        return (flash_attention_bshd(q, k, v, causal=causal,
                                     interpret=True) * g).sum()

    def ref_loss(q, k, v):
        return (_ref_attention(q, k, v, causal) * g).sum()

    dq, dk, dv = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=5e-3,
                               atol=5e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=5e-3,
                               atol=5e-3)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=5e-3,
                               atol=5e-3)


def test_flash_bf16():
    rng = np.random.RandomState(3)
    shape = (1, 128, 2, 64)
    q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    k = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    v = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    out = flash_attention_bshd(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _ref_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=5e-2,
                               atol=5e-2)


def test_flash_forward_unaligned_seq_noncausal():
    """Regression: padded key positions must be masked out of the softmax in
    the non-causal path too."""
    rng = np.random.RandomState(4)
    shape = (1, 130, 2, 32)  # 130 % 128 != 0 → 126 padded keys
    q = jnp.asarray(rng.randn(*shape), jnp.float32)
    k = jnp.asarray(rng.randn(*shape), jnp.float32)
    v = jnp.asarray(rng.randn(*shape), jnp.float32)
    out = flash_attention_bshd(q, k, v, causal=False, interpret=True)
    ref = _ref_attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


# ------------------------------------- the scheduled blocks (ISSUE 28)

def _qkv(shape, sk, dtype, seed):
    rng = np.random.RandomState(seed)
    b, s, h, d = shape
    kshape = (b, sk or s, h, d)
    return (jnp.asarray(rng.randn(*shape), dtype),
            jnp.asarray(rng.randn(*kshape), dtype),
            jnp.asarray(rng.randn(*kshape), dtype),
            jnp.asarray(rng.randn(*shape), dtype))


def _fwd_and_grads(attend, q, k, v, g):
    """-> (o, dq, dk, dv) in float32 for the cotangent ``g``."""
    o, pull = jax.vjp(attend, q, k, v)
    return tuple(np.asarray(x, np.float32) for x in (o, *pull(g.astype(o.dtype))))


# shape, key length (None: as the queries), causal. What each is there for:
# 1024 x 128 is the cells' head at half their sequence (one 1024 x 1024
# block whose diagonal is trimmed in four chunks of 256 queries); 1000 x 64
# pads to 1024 and masks 24 keys inside a block larger than 128; 100 is a
# sequence shorter than one block; 256 against 640 keys is non-causal cross
# attention with different blocks on the two axes; 2304 x 2 heads of 16
# makes two 1152 blocks a head (a dead step, a plain block under the
# diagonal, chunks of 128); 256 against 640 keys under a causal mask is a
# block that is not square, masked whole.
SCHEDULED = [
    ((1, 1024, 2, 128), None, True), ((1, 1024, 2, 128), None, False),
    ((1, 1000, 2, 64), None, True), ((1, 1000, 2, 64), None, False),
    ((1, 100, 2, 32), None, True), ((1, 256, 2, 64), 640, False),
    ((1, 2304, 2, 16), None, True), ((1, 256, 2, 64), 640, True)]


@pytest.mark.parametrize("shape,sk,causal", SCHEDULED)
def test_scheduled_blocks_match_reference_f32(shape, sk, causal):
    """Forward and all three gradients at the blocks ``block_schedule``
    picks, float32 in and out: nothing is rounded, so the tolerances are
    those of the 128 x 128 tests above."""
    q, k, v, g = _qkv(shape, sk, jnp.float32, 5)
    got = _fwd_and_grads(lambda q, k, v: flash_attention_bshd(
        q, k, v, causal=causal, interpret=True), q, k, v, g)
    ref = _fwd_and_grads(lambda q, k, v: _ref_attention(q, k, v, causal),
                         q, k, v, g)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("shape,sk,causal", SCHEDULED[:3])
def test_scheduled_blocks_match_reference_bf16(shape, sk, causal):
    """bf16 operands go into the products as they are and ``p`` / ``ds``
    are rounded to bf16 for theirs: against the float32 reference on the
    same (bf16-valued) inputs, unit-normal data, ``o`` stays within 2e-2
    and the gradients within 4e-2 absolute (measured 0.7e-2 to 1.3e-2:
    one bf16 rounding, 2^-9 relative, of terms of a few units)."""
    q, k, v, g = _qkv(shape, sk, jnp.bfloat16, 6)
    got = _fwd_and_grads(lambda q, k, v: flash_attention_bshd(
        q, k, v, causal=causal, interpret=True), q, k, v, g)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
    ref = _fwd_and_grads(lambda q, k, v: _ref_attention(q, k, v, causal),
                         *f32)
    for name, a, b, tol in zip(("o", "dq", "dk", "dv"), got, ref,
                               (2e-2, 4e-2, 4e-2, 4e-2)):
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("shape,sk,causal", SCHEDULED[:4])
def test_scheduled_blocks_agree_with_forced_128(shape, sk, causal):
    """The explicit ``block_q`` / ``block_k`` override still forces all
    three kernels, and its results agree with the scheduled blocks'."""
    q, k, v, g = _qkv(shape, sk, jnp.float32, 7)
    auto = _fwd_and_grads(lambda q, k, v: flash_attention_bshd(
        q, k, v, causal=causal, interpret=True), q, k, v, g)
    forced = _fwd_and_grads(lambda q, k, v: flash_attention_bshd(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True),
        q, k, v, g)
    for name, a, b in zip(("o", "dq", "dk", "dv"), auto, forced):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


def test_float32_inputs_are_never_narrowed():
    """The operand dtype follows the inputs. With float32 q/k/v no value
    in the three kernels is narrower than 32 bits. With bf16 inputs the
    products take bf16 operands, and every scratch buffer (``m``, ``l``,
    the accumulators), every product's and every ``exp``'s result, and
    the ``lse`` / ``delta`` blocks are float32 all the same."""
    import re

    def kernels_text(dtype):
        q, k, v, g = _qkv((1, 256, 1, 64), None, dtype, 0)

        def step(q, k, v):
            o, pull = jax.vjp(lambda q, k, v: flash_attention_bshd(
                q, k, v, causal=True, interpret=True), q, k, v)
            return o, pull(g)
        return str(jax.make_jaxpr(step)(q, k, v))

    text = kernels_text(jnp.float32)
    assert text.count("pallas_call") == 3
    assert not re.search(r"\b(bf16|f16)\[", text)
    text = kernels_text(jnp.bfloat16)
    assert re.search(r"Ref\{bf16\[1,256,64\]\}", text)     # operands
    scratch = re.findall(r"Ref<vmem>\{(\w+)\[", text)
    assert scratch and set(scratch) == {"f32"}
    results = re.findall(r":(\w+)\[[\d,]*\] = (?:dot_general\[|exp )", text)
    assert len(results) >= 13 and set(results) == {"f32"}
    # lse out of the forward and into dq (lane-replicated), lse and delta
    # into dk/dv (sequence on the lanes)
    assert "Ref{f32[1,256,128]}" in text and "Ref{f32[1,1,256]}" in text
    assert not re.search(r"Ref\{bf16\[1,(256,128|1,256)\]\}", text)


@pytest.mark.parametrize("sq,sk,d,dtype", [
    (2048, 2048, 128, jnp.bfloat16), (2048, 2048, 128, jnp.float32),
    (64, 64, 64, jnp.float32), (100, 100, 32, jnp.float32),
    (1000, 1000, 128, jnp.bfloat16), (1152, 1152, 128, jnp.bfloat16),
    (130, 130, 32, jnp.float32), (640, 1408, 64, jnp.bfloat16),
    (8192, 8192, 128, jnp.bfloat16), (4, 4, 8, jnp.float32)])
def test_block_schedule(sq, sk, d, dtype):
    """``block_schedule`` alone: blocks divide the one padded length of
    their axis and never exceed it, the padding is under one block and a
    multiple of 128 (or the sequence itself below 128, as before this
    schedule), the chunk of queries divides its block, the stated VMEM
    budget holds, and the cells' shape takes 32 steps a call, not 8,192."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    sch = fa.block_schedule(sq, sk, d, dtype)
    for s, pad in ((sq, sch.sq), (sk, sch.sk)):
        if s < 128:
            assert pad == max(s, 8)             # one block, today's
        else:
            assert pad % 128 == 0 and s <= pad
    itemsize = jnp.dtype(dtype).itemsize
    for kernel in fa.KERNELS:
        bq, bk, chunk = getattr(sch, kernel)
        assert sch.sq % bq == 0 and sch.sk % bk == 0
        assert bq <= sch.sq and bk <= sch.sk
        assert sch.sq - sq < bq and sch.sk - sk < bk
        assert bq % chunk == 0 and (chunk <= fa.CHUNK or chunk == bq)
        assert fa._step_vmem_bytes(kernel, bq, bk, chunk, d, itemsize) \
            <= fa.VMEM_BUDGET
    steps = fa.grid_steps(sch, True, bh=32)
    if (sq, sk) == (2048, 2048):
        for kernel, (live, dead) in steps.items():
            assert live + dead <= 1024, (kernel, live, dead)
            assert dead < live
    if max(sq, sk) < 128:
        # what the code before the schedule did: min(128, max(s, 8))
        assert sch.fwd[:2] == sch.dq[:2] == sch.dkv[:2] == \
            (max(sq, 8), max(sk, 8))
        assert all(v == (32, 0) for v in steps.values())


def test_block_schedule_ignores_everything_but_the_call(monkeypatch):
    """No environment variable, flag or cache feeds the schedule: the
    same arguments give the same answer under any environment."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    before = fa.block_schedule(2048, 2048, 128, jnp.bfloat16)
    for name in ("PADDLE_TPU_KERNELS", "PADDLE_TPU_FLASH_BLOCK",
                 "PADDLE_TPU_KERNELS_CACHE", "XLA_FLAGS"):
        monkeypatch.setenv(name, "128")
    assert fa.block_schedule(2048, 2048, 128, jnp.bfloat16) == before


# ---------------------------- the schedule in the trace buffer (ISSUE 28)

def test_schedule_event_recorded_once_per_traced_call():
    """With the buffer on, tracing a call records exactly one
    ``flash_attention.schedule`` event carrying what ``block_schedule``
    returns; running the compiled program again records nothing."""
    from paddle_tpu.observability import tracing
    from paddle_tpu.ops.pallas import flash_attention as fa
    q, k, v, _ = _qkv((2, 1000, 2, 64), None, jnp.bfloat16, 0)
    buf = tracing.start()
    try:
        step = jax.jit(lambda q, k, v: flash_attention_bshd(
            q, k, v, causal=True, interpret=True))
        step(q, k, v)
        step(q, k, v)                      # cached: no second trace
    finally:
        tracing.stop()
    events = [e for e in buf.events if e["name"] == "flash_attention.schedule"]
    assert len(events) == 1
    ev = events[0]
    sch = fa.block_schedule(1000, 1000, 64, jnp.bfloat16)
    steps = fa.grid_steps(sch, True, bh=4)
    assert ev["cat"] == "kernels" and ev["ph"] == "X" and ev["dur"] == 0
    assert ev["args"] == {
        "shape": [2, 1000, 2, 64], "sk": 1000, "dtype": "bfloat16",
        "causal": True, "padded": [sch.sq, sch.sk],
        "fwd": list(sch.fwd), "bwd_dq": list(sch.dq),
        "bwd_dkv": list(sch.dkv),
        "steps_live": {n: s[0] for n, s in steps.items()},
        "steps_dead": {n: s[1] for n, s in steps.items()}}


def test_schedule_event_off_makes_no_call_into_tracing(monkeypatch):
    """Tracing off (the default): the kernel module passes the buffer's
    one gate and calls nothing in ``tracing`` — no buffer method, no
    feed, no phase — and does not build the event either."""
    from paddle_tpu.observability import tracing
    from paddle_tpu.ops.pallas import flash_attention as fa
    tracing.stop()
    calls = []

    def count(name):
        def h(*a, **k):
            calls.append(name)
        return h

    for name in ("add_complete", "span", "phase", "req_event", "start",
                 "get_buffer", "enabled"):
        monkeypatch.setattr(tracing, name, count(name))
    monkeypatch.setattr(tracing.TraceBuffer, "add", count("TraceBuffer.add"))
    monkeypatch.setattr(fa, "_record_schedule", count("_record_schedule"))
    q, k, v, _ = _qkv((1, 128, 2, 32), None, jnp.float32, 0)
    jax.jit(lambda q, k, v: flash_attention_bshd(
        q, k, v, causal=True, interpret=True))(q, k, v)
    assert calls == []


# -------------------------------------------- sharded flash (shard_map)

def _mesh(shape, names):
    devs = np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    return jax.sharding.Mesh(devs, names)


# mesh shape, axis names, batch, the batch the kernel must see on a rank
SHARDED_CASES = [
    pytest.param((2, 4), ("data", "model"), 4, 2, id="data-model"),
    pytest.param((2, 2), ("sharding", "model"), 4, 2, id="sharding-model"),
    pytest.param((2, 2, 2), ("data", "sharding", "model"), 4, 1,
                 id="data-sharding-model"),
    # the fleet mesh's own order and its idle axes
    pytest.param((1, 1, 2, 1, 2),
                 ("data", "pipe", "sharding", "sep", "model"), 4, 2,
                 id="fleet-mp2-sh2"),
    # 6 sequences over data 2 x sharding 2: the right-most axis is
    # dropped, so a rank keeps 3 of them
    pytest.param((2, 2, 2), ("data", "sharding", "model"), 6, 3,
                 id="batch-not-divisible-drops-sharding"),
    # 3 over 2: no axis is left, every rank attends the whole batch
    pytest.param((2, 2), ("sharding", "model"), 3, 3,
                 id="batch-not-divisible-falls-back"),
    pytest.param((2, 2), ("sharding", "model"), 1, 1, id="batch-of-one"),
    pytest.param((4, 1), ("sharding", "model"), 4, 1, id="no-model-axis"),
]


@pytest.mark.parametrize("shape,names,batch,local", SHARDED_CASES)
def test_sharded_flash_matches_unsharded(shape, names, batch, local):
    """Heads over 'model', batch over every axis that splits it ('data',
    'sharding'; SNIPPETS [2] shape): the shard_map'd kernel equals the
    unsharded impl in its output and in the gradients of q, k and v --
    attention is local to a sequence and a head -- and each rank is handed
    its own sequences only."""
    from paddle_tpu.ops.pallas.flash_attention import sharded_flash_attention
    mesh = _mesh(shape, names)
    rng = np.random.RandomState(0)
    qkv_shape = (batch, 32, 8, 32)
    q, k, v, w = (jnp.asarray(rng.randn(*qkv_shape), jnp.float32)
                  for _ in range(4))
    seen = []

    def impl(q, k, v):  # the CPU mesh cannot run the Mosaic kernel
        seen.append(q.shape)
        return _ref_attention(q, k, v, True)

    fa = sharded_flash_attention(mesh, impl=impl)
    out = fa(q, k, v)
    heads = 8 // mesh.shape["model"]
    assert seen == [(local, 32, heads, 32)]
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(impl(q, k, v)), rtol=1e-5,
                               atol=1e-5)
    # gradients flow through shard_map (training path requirement)
    grads = jax.grad(lambda *a: jnp.sum(fa(*a) * w), argnums=(0, 1, 2))
    ref = jax.grad(lambda *a: jnp.sum(impl(*a) * w), argnums=(0, 1, 2))
    for g, r in zip(grads(q, k, v), ref(q, k, v)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("degrees,batch,axes", [
    ({"data": 2, "model": 4}, 4, ("data",)),
    ({"data": 1, "sharding": 2, "model": 2}, 4, ("sharding",)),
    ({"data": 2, "sharding": 2, "model": 2}, 8, ("data", "sharding")),
    ({"data": 2, "sharding": 2, "model": 2}, 6, ("data",)),
    ({"data": 2, "sharding": 2, "model": 2}, 3, ()),
    ({"data": 1, "sharding": 2, "model": 2}, 1, ()),
    # the axes the batch never rides are never chosen
    ({"data": 1, "pipe": 2, "sep": 2, "model": 2}, 4, ()),
])
def test_flash_batch_axes_come_from_mesh_and_batch(degrees, batch, axes):
    from types import SimpleNamespace
    from paddle_tpu.ops.pallas.flash_attention import flash_batch_axes
    assert flash_batch_axes(SimpleNamespace(shape=degrees), batch) == axes


def test_sharded_flash_degenerate_mesh_returns_impl():
    from paddle_tpu.ops.pallas.flash_attention import sharded_flash_attention
    mesh = _mesh((1, 1), ("data", "model"))

    def impl(q, k, v):
        return q

    assert sharded_flash_attention(mesh, impl=impl) is impl


@pytest.mark.parametrize("hybrid,batch,local", [
    # ~8s: tier-1 sits at the 870s budget edge (slowest_tests gate); full
    # coverage stays in the slow suite
    pytest.param({"dp_degree": 2, "mp_degree": 4, "pp_degree": 1}, 8,
                 (4, 16, 2, 8), marks=pytest.mark.slow, id="dp2-mp4"),
    # the hybrid step's kind of mesh: the batch rides 'sharding'
    pytest.param({"dp_degree": 1, "mp_degree": 2, "pp_degree": 1,
                  "sharding_degree": 4}, 8, (2, 16, 4, 8), id="sh4-mp2"),
    # eight devices left over two degrees of 2: fleet fills 'data' with 2,
    # and the batch is split over data x sharding
    pytest.param({"mp_degree": 2, "pp_degree": 1, "sharding_degree": 2}, 4,
                 (1, 16, 4, 8), marks=pytest.mark.slow, id="dp2-sh2-mp2"),
])
def test_gpt_attention_uses_sharded_flash_under_tp(hybrid, batch, local):
    """GPT's training attention routes through the shard_map'd flash path
    when a TP mesh is active and the kernel is eligible — asserted by
    injecting a marking impl through the test hook, which also sees the
    shape a rank is handed (its own sequences and heads) — and the loss
    stays finite with gradients flowing to the TP-sharded qkv weights."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet, shard_batch
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, \
        GPTPretrainingCriterion
    from paddle_tpu.models.gpt import GPTAttention

    paddle.seed(0)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = hybrid
    fleet.init(is_collective=True, strategy=strategy)
    from paddle_tpu.distributed.topology import \
        get_hybrid_communicate_group
    hcg = get_hybrid_communicate_group()
    shapes = []

    def marking_impl(q, k, v):
        shapes.append(q.shape)
        return _ref_attention(q, k, v, True)

    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=1,
                    num_heads=8, max_seq_len=32, dropout=0.0,
                    tensor_parallel=True)
    GPTAttention._sharded_impl_override = marking_impl
    try:
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 128, (batch, 16))
            .astype("int32"))
        if hybrid.get("sharding_degree", 1) > 1:
            # as the benchmark's hybrid step places its batch
            ids = shard_batch(ids, hcg.get_sharding_parallel_group())
        loss = crit(model(ids), ids)
        assert shapes and set(shapes) == {local}, shapes
        assert np.isfinite(float(loss.numpy()))
        loss.backward()
        for p in model.parameters():
            if p._grad is not None:
                assert bool(jnp.all(jnp.isfinite(p._grad)))
    finally:
        GPTAttention._sharded_impl_override = None
