"""Pallas fused AdamW + RMSNorm parity (VERDICT r2 #6).

Interpret-mode kernels vs the jnp compositions (reference:
fused_adam_kernel.cu, fusion/gpu/fused_layernorm_kernel.cu)."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
from paddle_tpu.ops.pallas.rms_norm import rms_norm


def test_fused_adamw_matches_jnp_composition():
    rng = np.random.RandomState(0)
    n = 1000  # deliberately not lane-aligned: exercises padding
    w = jnp.asarray(rng.randn(n), jnp.float32)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(rng.randn(n)) * 0.01, jnp.float32)
    lr, b1, b2, eps, wd, t = 1e-3, 0.9, 0.999, 1e-8, 0.01, 7
    bc1 = 1.0 / (1 - b1 ** t)
    bc2 = 1.0 / (1 - b2 ** t)

    w2, m2, v2 = fused_adamw(w, g, m, v, lr, b1, b2, eps, wd, bc1, bc2,
                             interpret=True)

    # f32 scalars, matching the kernel's SMEM operands (0.999 as f32 differs
    # from the f64 python literal at the 1e-5 level)
    lrf, b1f, b2f, epsf, wdf, bc1f, bc2f = (
        np.float32(s) for s in (lr, b1, b2, eps, wd, bc1, bc2))
    wref = w * (np.float32(1) - lrf * wdf)
    mref = b1f * m + (np.float32(1) - b1f) * g
    vref = b2f * v + (np.float32(1) - b2f) * g * g
    wref = wref - lrf * (mref * bc1f) / (jnp.sqrt(vref * bc2f) + epsf)
    np.testing.assert_allclose(np.asarray(m2), np.asarray(mref), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(vref), rtol=1e-6,
                               atol=1e-8)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(wref), rtol=1e-5,
                               atol=1e-7)


def test_fused_adamw_2d_bf16_param():
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(48, 96), jnp.bfloat16)
    g = jnp.asarray(rng.randn(48, 96), jnp.float32)
    m = jnp.zeros((48, 96), jnp.float32)
    v = jnp.zeros((48, 96), jnp.float32)
    w2, m2, v2 = fused_adamw(w, g, m, v, 1e-2, 0.9, 0.999, 1e-8, 0.0,
                             1.0 / (1 - 0.9), 1.0 / (1 - 0.999),
                             interpret=True)
    assert w2.dtype == jnp.bfloat16 and w2.shape == (48, 96)
    mref = 0.1 * np.asarray(g, np.float32)
    np.testing.assert_allclose(np.asarray(m2), mref, rtol=1e-5)


def test_optimizer_fused_flag_matches_default():
    """AdamW(use_fused=True) in interpret-capable (CPU) mode must produce
    the same trajectory as the jnp path."""
    rng = np.random.RandomState(2)
    xw = rng.randn(64, 32).astype("float32")
    yw = rng.randn(64, 8).astype("float32")

    def run(fused):
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F
        paddle.seed(3)
        mdl = nn.Linear(32, 8)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=mdl.parameters(),
                                     weight_decay=0.01)
        # force the fused path through interpret mode by monkey flag
        opt.use_fused = False if not fused else None
        if fused:
            # patch fused_adamw to interpret mode for the CPU test
            from paddle_tpu.ops.pallas import fused_adamw as fa
            orig = fa.fused_adamw
            import functools
            fa_patched = functools.partial(orig, interpret=True)
            import paddle_tpu.optimizer.optimizers as om
            opt.use_fused = True
            opt._FUSED_MIN_SIZE = 1
            import paddle_tpu.ops.pallas.fused_adamw as mod
            mod_orig = mod.fused_adamw
            mod.fused_adamw = fa_patched
        try:
            for _ in range(3):
                loss = F.mse_loss(mdl(paddle.to_tensor(xw)),
                                  paddle.to_tensor(yw))
                loss.backward()
                opt.step()
                opt.clear_grad()
        finally:
            if fused:
                mod.fused_adamw = mod_orig
        return mdl.weight.numpy()

    np.testing.assert_allclose(run(True), run(False), rtol=2e-5, atol=1e-6)


def test_rms_norm_pallas_parity_and_grads():
    import jax
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(6, 256), jnp.float32)
    w = jnp.asarray(rng.randn(256), jnp.float32)
    b = jnp.asarray(rng.randn(256), jnp.float32)
    eps = 1e-6
    out = rms_norm(x, w, b, eps=eps, interpret=True)
    ref = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + eps) \
        * np.asarray(w) + np.asarray(b)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)

    # grads vs jax autodiff of the composition
    def comp(x, w, b):
        inv = 1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return jnp.sum((x * inv * w + b) ** 2)

    gx, gw, gb = jax.grad(
        lambda x, w, b: jnp.sum(
            rms_norm(x, w, b, eps=eps, interpret=True) ** 2),
        argnums=(0, 1, 2))(x, w, b)
    rx, rw, rb = jax.grad(comp, argnums=(0, 1, 2))(x, w, b)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb), rtol=1e-4,
                               atol=1e-4)


def test_incubate_fused_rms_norm_pallas_path():
    rng = np.random.RandomState(5)
    x = paddle.to_tensor(rng.randn(4, 128).astype("float32"),
                         stop_gradient=False)
    w = paddle.to_tensor(rng.randn(128).astype("float32"),
                         stop_gradient=False)
    out = paddle.incubate.fused_rms_norm(x, w, interpret=True)
    ref = paddle.incubate.fused_rms_norm(x, w, use_pallas=False)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-5,
                               atol=1e-5)
    out.sum().backward()
    assert x._grad is not None and w._grad is not None


def test_paged_attention_kernel_matches_reference():
    """Paged-KV decode attention (reference capability:
    block_multi_head_attention_kernel.cu)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference,
    )
    rng = np.random.RandomState(0)
    B, H, D, PS, NP, MP = 3, 8, 64, 16, 20, 4
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    kc = jnp.asarray(rng.randn(NP, PS, H, D), jnp.float32)
    vc = jnp.asarray(rng.randn(NP, PS, H, D), jnp.float32)
    bt = jnp.asarray(rng.permutation(NP)[:B * MP].reshape(B, MP), jnp.int32)
    cl = jnp.asarray([50, 17, 64], jnp.int32)
    ref = paged_attention_reference(q, kc, vc, bt, cl)
    out = paged_attention(q, kc, vc, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
    # independent numpy check on the short sequence
    k1 = np.asarray(kc)[np.asarray(bt)[1]].reshape(-1, H, D)[:17]
    v1 = np.asarray(vc)[np.asarray(bt)[1]].reshape(-1, H, D)[:17]
    s = np.einsum("hd,khd->hk", np.asarray(q)[1], k1) / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o1 = np.einsum("hk,khd->hd", p, v1)
    np.testing.assert_allclose(np.asarray(out)[1], o1, rtol=1e-4, atol=1e-5)


def test_incubate_paged_attention_api():
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    B, H, D, PS, NP, MP = 2, 4, 32, 8, 8, 2
    q = paddle.to_tensor(rng.randn(B, H, D).astype("float32"))
    kc = paddle.to_tensor(rng.randn(NP, PS, H, D).astype("float32"))
    vc = paddle.to_tensor(rng.randn(NP, PS, H, D).astype("float32"))
    bt = paddle.to_tensor(np.arange(B * MP).reshape(B, MP).astype("int32"))
    cl = paddle.to_tensor(np.array([12, 16], np.int32))
    out = paddle.incubate.paged_attention(q, kc, vc, bt, cl,
                                          interpret=True)
    ref = paddle.incubate.paged_attention(q, kc, vc, bt, cl,
                                          use_pallas=False)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-5,
                               atol=1e-5)


def test_paged_attention_grads_flow():
    """Review r3 finding: the scalar-prefetch kernel has no JVP rule — the
    trainable wrapper must still deliver grads (reference-path backward)."""
    rng = np.random.RandomState(2)
    B, H, D, PS, NP, MP = 2, 4, 32, 8, 8, 2
    q = paddle.to_tensor(rng.randn(B, H, D).astype("float32"),
                         stop_gradient=False)
    kc = paddle.to_tensor(rng.randn(NP, PS, H, D).astype("float32"),
                          stop_gradient=False)
    vc = paddle.to_tensor(rng.randn(NP, PS, H, D).astype("float32"))
    bt = paddle.to_tensor(np.arange(B * MP).reshape(B, MP).astype("int32"))
    cl = paddle.to_tensor(np.array([12, 16], np.int32))
    out = paddle.incubate.paged_attention(q, kc, vc, bt, cl, interpret=True)
    out.sum().backward()
    assert q._grad is not None and np.isfinite(np.asarray(q._grad)).all()
    assert kc._grad is not None


def test_paged_attention_padded_table_and_zero_context():
    """Advisor r3: sentinel-padded block tables (-1 / out-of-range ids)
    must not read out-of-bounds pages, and context_len == 0 must yield
    zeros (not an average of garbage V pages) on both paths."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference,
    )
    rng = np.random.RandomState(3)
    B, H, D, PS, NP, MP = 3, 4, 32, 8, 6, 4
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    kc = jnp.asarray(rng.randn(NP, PS, H, D), jnp.float32)
    vc = jnp.asarray(rng.randn(NP, PS, H, D), jnp.float32)
    # rows: valid ids then sentinel padding (-1 and >= num_pages)
    bt = jnp.asarray([[0, 1, -1, -1],
                      [2, 3, 99, 99],
                      [4, -1, -1, -1]], jnp.int32)
    cl = jnp.asarray([12, 16, 0], jnp.int32)
    ref = paged_attention_reference(q, kc, vc, bt, cl)
    out = paged_attention(q, kc, vc, bt, cl, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out)[2], 0.0)  # empty sequence
    np.testing.assert_allclose(np.asarray(ref)[2], 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # parity with an in-range-padded table (padding must not matter)
    bt_safe = jnp.asarray([[0, 1, 0, 0],
                           [2, 3, 0, 0],
                           [4, 0, 0, 0]], jnp.int32)
    out_safe = paged_attention(q, kc, vc, bt_safe, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_safe),
                               rtol=1e-6, atol=1e-6)


# rounds of the ragged kernel: (token pad, page size, rows as (query
# tokens, context after them), 0 tokens = an unused row); heads of 128 as
# GPT's, 4 query heads over 2 KV heads
RAGGED_ROUNDS = {
    "decode_rows": (8, 16, [(1, 1), (1, 16), (1, 17), (1, 300)]),
    "one_chunk_of_64": (64, 16, [(64, 64)]),
    "decode_and_chunks": (128, 16, [(1, 20), (1, 129), (64, 64),
                                    (1, 7), (40, 190)]),
    # 13 tokens ending at 301: the last of its three 8-page blocks holds
    # three pages, the last page 13 of its 16 slots
    "prompt_tail": (32, 16, [(13, 301)]),
    "row_longer_than_block_q": (64, 16, [(1, 33), (50, 170)]),
    # rows after the last used one unused, 21 pad tokens after its tokens
    "unused_and_pad_rows": (32, 16, [(1, 40), (9, 9), (1, 3), (0, 0),
                                     (0, 0), (0, 0)]),
    "pad_8": (8, 16, [(1, 100), (5, 5), (2, 60)]),
    "pad_16": (16, 16, [(1, 100), (12, 40), (1, 2)]),
    "pad_32": (32, 16, [(1, 100), (25, 25), (1, 2)]),
    "pad_64": (64, 16, [(1, 100), (33, 80), (20, 20)]),
    "pad_128": (128, 16, [(1, 100), (64, 140), (40, 40), (1, 65)]),
    "page_256": (32, 256, [(1, 300), (20, 700), (1, 256), (0, 0)]),
}


@pytest.mark.parametrize("case", ["mixed"] + list(RAGGED_ROUNDS))
def test_ragged_paged_attention_kernel_matches_reference(case):
    """The ragged kernel (interpret mode) matches the gather/segment
    reference: ``mixed`` is a small mixed launch (a decode row, a
    whole-prompt prefill, a mid-stream chunk continuation, GQA pools, an
    unused row and padded tail tokens); the others are rounds of work items at heads of
    128: decode rows, chunks, a prompt tail behind a cached head whose
    last page block is partly filled, rows longer than an item, unused
    rows, every pad of the engine's ladder and pages of 16 and 256. Pad
    tokens come back zeroed, never NaN."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.ragged_attention import (
        ragged_paged_attention, ragged_paged_attention_reference,
        ragged_row_index,
    )
    rng = np.random.RandomState(4)
    if case == "mixed":
        H, KVH, D, PS, NP, MP, T = 4, 2, 32, 4, 16, 4, 16
        bt = jnp.asarray(rng.randint(1, NP, size=(4, MP)), jnp.int32)
        rs = jnp.asarray([0, 1, 6, T], jnp.int32)   # row 3 unused
        rl = jnp.asarray([1, 5, 3, 0], jnp.int32)
        kl = jnp.asarray([7, 5, 9, 0], jnp.int32)
        used = 9
    else:
        T, PS, rows = RAGGED_ROUNDS[case]
        H, KVH, D = 4, 2, 128
        MP = max(-(-kv // PS) for _, kv in rows)
        # every row its own pages, the scrap page 0 behind its table
        NP = 1 + len(rows) * MP
        bt = np.zeros((len(rows), MP), np.int32)
        for i, (_, kv) in enumerate(rows):
            n = -(-kv // PS)
            bt[i, :n] = 1 + rng.permutation(len(rows) * MP)[:n]
        starts = np.cumsum([0] + [n for n, _ in rows])[:-1]
        rs = jnp.asarray([s if n else T for s, (n, _) in zip(starts, rows)],
                         jnp.int32)
        rl = jnp.asarray([n for n, _ in rows], jnp.int32)
        kl = jnp.asarray([kv for _, kv in rows], jnp.int32)
        bt = jnp.asarray(bt)
        used = int(np.sum(rl))
    q = jnp.asarray(rng.randn(T, H, D), jnp.float32)
    kc = jnp.asarray(rng.randn(NP, PS, KVH, D), jnp.float32)
    vc = jnp.asarray(rng.randn(NP, PS, KVH, D), jnp.float32)
    ref = np.asarray(
        ragged_paged_attention_reference(q, kc, vc, rs, rl, kl, bt))
    out = np.asarray(
        ragged_paged_attention(q, kc, vc, rs, rl, kl, bt,
                               interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[used:], 0.0)    # padded tail zeroed
    if case == "mixed":
        # the shared segment decomposition is the contract both sides use
        rid, pos, valid = ragged_row_index(rs, rl, kl, T)
        assert np.asarray(rid)[:9].tolist() == [0, 1, 1, 1, 1, 1, 2, 2, 2]
        assert np.asarray(pos)[:9].tolist() == [6, 0, 1, 2, 3, 4, 6, 7, 8]
        assert not bool(np.asarray(valid)[9:].any())


def test_ragged_item_schedule_follows_the_shapes():
    """``item_schedule`` reads the page size, the head width and the token
    pad and nothing else: the same shapes give the same blocks, a decode
    pad small items and a chunk pad larger ones, a step some 128 keys of
    a 128-wide head. The launch's item count is ``rows + T // block_q``,
    and an unused item plans no page."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.mla_ragged_attention import work_items
    from paddle_tpu.ops.pallas.ragged_attention import (item_plan,
                                                        item_schedule)
    assert item_schedule(16, 128, 32) == item_schedule(16, 128, 32) == (8, 8)
    assert item_schedule(16, 128, 128) == (32, 8)
    assert item_schedule(16, 128, 8) == (8, 8)
    assert item_schedule(256, 128, 64) == (32, 1)
    assert item_schedule(16, 64, 32) == (8, 16)
    assert item_schedule(16, 128, 4) == (4, 8)
    for T, rows in ((32, [(1, 30), (1, 200), (0, 0)]),
                    (128, [(1, 30), (64, 64), (40, 301), (0, 0)])):
        block_q, pages = item_schedule(16, 128, T)
        rs = np.cumsum([0] + [n for n, _ in rows])[:-1]
        rl = np.array([n for n, _ in rows], np.int32)
        kl = np.array([kv for _, kv in rows], np.int32)
        row, pos0, nq, tok = work_items(
            jnp.asarray(np.where(rl > 0, rs, T), jnp.int32),
            jnp.asarray(rl), jnp.asarray(kl), T, block_q)
        assert row.shape[0] == len(rows) + T // block_q
        nq, pos0 = np.asarray(nq), np.asarray(pos0)
        # the pages each item reads: its last token's, none if unused
        n_pages = np.where(nq > 0, (pos0 + nq - 1) // 16 + 1, 0)
        assert (n_pages[nq == 0] == 0).all()
        items, blocks = item_plan(rl, kl, 16, block_q, pages)
        assert items == int((nq > 0).sum())
        assert blocks == int(np.sum(-(-n_pages // pages)))
    # a decode row of 300: 19 pages, three blocks; a 40-token tail ending
    # at 301 at block_q 32: two items, both three blocks
    assert item_plan([1, 40, 0], [300, 301, 0], 16, 32, 8) == (3, 9)


def test_asp_indivisible_dim_warns():
    """Advisor r3: non-divisible last dim silently skipped pruning."""
    import warnings
    import paddle_tpu.nn as nn
    from paddle_tpu.incubate import asp

    model = nn.Sequential(nn.Linear(6, 5))  # 5 % 4 != 0
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        asp.prune_model(model, n=2, m=4)
    assert any("not divisible" in str(x.message) for x in w)


@pytest.mark.slow
def test_fused_adamw_composes_with_zero_sharding():
    """VERDICT r3 weak #6: fused AdamW must stay ACTIVE under ZeRO — the
    kernel shard_maps over each device's local shard of the merged spec.
    Parity vs the jnp path under identical sharding, and the kernel must
    actually run."""
    import functools

    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.sharding import (
        DygraphShardingOptimizer,
    )
    import paddle_tpu.ops.pallas.fused_adamw as mod

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 1,
                               "sharding_degree": 8, "sep_degree": 1,
                               "mp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    rng = np.random.RandomState(4)
    xw = rng.randn(64, 32).astype("float32")
    yw = rng.randn(64, 8).astype("float32")
    calls = {"n": 0}

    def run(fused):
        paddle.seed(5)
        mdl = nn.Linear(32, 8)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=mdl.parameters(),
                                     weight_decay=0.01)
        opt = DygraphShardingOptimizer(
            opt, group=hcg.get_sharding_parallel_group())
        inner = opt._inner
        inner.use_fused = bool(fused)
        orig = mod.fused_adamw
        if fused:
            inner._FUSED_MIN_SIZE = 1

            def counting(*a, **k):
                calls["n"] += 1
                return orig(*a, interpret=True, **k)

            mod.fused_adamw = counting
        try:
            for _ in range(3):
                loss = F.mse_loss(mdl(paddle.to_tensor(xw)),
                                  paddle.to_tensor(yw))
                loss.backward()
                opt.step()
                opt.clear_grad()
        finally:
            mod.fused_adamw = orig
        return mdl.weight.numpy()

    fused_w = run(True)
    assert calls["n"] > 0, "fused kernel never ran under ZeRO sharding"
    np.testing.assert_allclose(fused_w, run(False), rtol=2e-5, atol=1e-6)


def test_pallas_layer_norm_matches_reference():
    """ops/pallas/layer_norm.py vs the jnp composition (interpret mode on
    CPU), incl. weight/bias combinations."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.layer_norm import layer_norm as pln
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(6, 128).astype("float32"))
    w = jnp.asarray((rng.rand(128) + 0.5).astype("float32"))
    b = jnp.asarray(rng.randn(128).astype("float32"))

    def ref(xa, wa, ba, eps=1e-5):
        mu = xa.mean(-1, keepdims=True)
        var = ((xa - mu) ** 2).mean(-1, keepdims=True)
        out = (xa - mu) / np.sqrt(np.asarray(var) + eps)
        if wa is not None:
            out = out * wa
        if ba is not None:
            out = out + ba
        return out

    for wa, ba in ((w, b), (w, None), (None, None)):
        got = np.asarray(pln(x, wa, ba, interpret=True))
        np.testing.assert_allclose(got, np.asarray(ref(x, wa, ba)),
                                   rtol=2e-5, atol=2e-5)


def test_pallas_layer_norm_grads_match_jnp():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.layer_norm import layer_norm as pln
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 64).astype("float32"))
    w = jnp.asarray((rng.rand(64) + 0.5).astype("float32"))
    b = jnp.asarray(rng.randn(64).astype("float32"))
    ct = jnp.asarray(rng.randn(4, 64).astype("float32"))

    def pallas_loss(xa, wa, ba):
        return (pln(xa, wa, ba, interpret=True) * ct).sum()

    def ref_loss(xa, wa, ba):
        mu = xa.mean(-1, keepdims=True)
        var = ((xa - mu) ** 2).mean(-1, keepdims=True)
        out = (xa - mu) * jax.lax.rsqrt(var + 1e-5) * wa + ba
        return (out * ct).sum()

    gp = jax.grad(pallas_loss, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-5)


def test_incubate_fused_layer_norm_pallas_path_trains():
    import paddle_tpu as paddle
    rng = np.random.RandomState(2)
    x = paddle.to_tensor(rng.randn(8, 128).astype("float32"))
    x.stop_gradient = False
    w = paddle.to_tensor((rng.rand(128) + 0.5).astype("float32"))
    w.stop_gradient = False
    b = paddle.to_tensor(np.zeros(128, "float32"))
    out = paddle.incubate.fused_layer_norm(x, w, b, interpret=True)
    ref = paddle.nn.functional.layer_norm(
        x.detach(), [128],
        paddle.to_tensor(w.numpy()), paddle.to_tensor(b.numpy()))
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-5,
                               atol=2e-5)
    out.sum().backward()
    assert x._grad is not None and w._grad is not None


def test_pallas_layer_norm_mixed_dtype_and_ragged_rows():
    """bf16 activations + f32 params (the standard TPU mix) must
    differentiate, and non-block-divisible row counts must pad, not
    build one giant VMEM block (review r5 findings)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.layer_norm import layer_norm as pln
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(7, 64), jnp.bfloat16)   # 7 % block != 0
    w = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(64), jnp.float32)
    out = pln(x, w, b, interpret=True, block_rows=4)
    assert out.shape == (7, 64) and out.dtype == jnp.bfloat16

    gx, gw, gb = jax.grad(
        lambda xa, wa, ba: pln(xa, wa, ba, interpret=True,
                               block_rows=4).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(x, w, b)
    assert gx.dtype == jnp.bfloat16
    assert gw.dtype == jnp.float32 and gb.dtype == jnp.float32


def test_pallas_rms_norm_ragged_rows_pad_grid():
    """rms_norm shares the pad-to-grid scaffold (review r5): odd row
    counts must not build one giant VMEM block."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.rms_norm import rms_norm as prms
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(7, 64).astype("float32"))
    w = jnp.asarray((rng.rand(64) + 0.5).astype("float32"))
    out = np.asarray(prms(x, w, block_rows=4, interpret=True))
    xa = np.asarray(x)
    inv = 1.0 / np.sqrt((xa * xa).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(out, xa * inv * np.asarray(w),
                               rtol=2e-5, atol=2e-5)
    assert out.shape == (7, 64)


def test_incubate_functional_fused_layer_norm_ignores_reference_extras():
    import paddle_tpu as paddle
    import paddle_tpu.incubate.nn.functional as IF
    rng = np.random.RandomState(5)
    x = paddle.to_tensor(rng.randn(4, 32).astype("float32"))
    w = paddle.to_tensor(np.ones(32, "float32"))
    b = paddle.to_tensor(np.zeros(32, "float32"))
    # reference-signature extras must be silently ignored, not TypeError
    out = IF.fused_layer_norm(x, w, b, quant_scale=-1,
                              norm_type="layernorm", interpret=True)
    np.testing.assert_allclose(out.numpy().mean(-1), 0.0, atol=1e-5)
