"""The latent-attention / dropless-expert decoder (``models/mla_moe.py``),
its router and expert layer (``incubate/moe.py``), its two kernels and its
latent page cache, on the CPU at a small size: 1 dense + 2 expert layers,
hidden 64, 4 heads, latent 16 + 8, 16 experts top-4, seeded weights. The
plain reference is the benchmark's own (``benchmark/reference``), which
imports nothing from the program."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.moe import (DroplessMoELayer, dispatch_plan,
                                     sigmoid_topk_route)
from paddle_tpu.models import (GPTForCausalLM, MLAMoEForCausalLM, gpt_tiny,
                               mla_moe_tiny)
from paddle_tpu.ops.pallas import mla_ragged_attention as mla
from paddle_tpu.ops.pallas import moe_grouped_matmul as gmm
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_cache import LayerState, PagedKVCache
from paddle_tpu.serving.scheduler import GenerationRequest

from benchmark.models import mla_moe as family

IDS = np.random.default_rng(0).integers(0, 256, size=64)


def build(seed=3, **kw):
    paddle.seed(seed)
    model = MLAMoEForCausalLM(mla_moe_tiny(**kw))
    model.eval()
    return model


def ref_logits(model, seq, where):
    return np.asarray(family.reference.logits_at(
        family.reference_weights(model), np.asarray(seq), list(where)))


# ------------------------------------------------ (a) the model's forward

def test_full_forward_matches_the_reference():
    model = build()
    with paddle.no_grad():
        got = model(paddle.to_tensor(IDS[None, :40].astype("int64")))
    want = ref_logits(model, IDS[:40], range(40))
    np.testing.assert_allclose(got.numpy()[0], want, atol=2e-4, rtol=0)


def test_generate_is_the_reference_argmax():
    model = build()
    out = model.generate(paddle.to_tensor(IDS[None, :8].astype("int64")),
                         max_new_tokens=6).numpy()[0]
    want = ref_logits(model, out, range(7, 13)).argmax(-1)
    assert out[8:].tolist() == want.tolist()


def test_yarn_frequencies_and_scale():
    """The published Kimi-K2 numbers: m = 0.1 ln 64 + 1 = 1.4159, the
    fast pairs keep their frequency and the slow ones are slowed 64
    times."""
    from paddle_tpu.models.mla_moe import yarn_inv_freq, yarn_mscale
    sc = {"factor": 64, "original_max_position_embeddings": 4096,
          "beta_fast": 32, "beta_slow": 1}
    assert abs(yarn_mscale(64, 1) - 1.4159) < 1e-4
    plain = yarn_inv_freq(64, 50000.0, None)
    yarn = yarn_inv_freq(64, 50000.0, sc)
    np.testing.assert_allclose(yarn[0], plain[0])
    np.testing.assert_allclose(yarn[-1], plain[-1] / 64, rtol=1e-6)
    np.testing.assert_allclose(
        yarn, family.reference.inv_freq(64, 50000.0, sc), rtol=1e-6)


# ---------------------------------- (b) chunked prefill + decode, engine

# float32 keeps the 2e-4 of the plain forward at every position. bfloat16
# has its own tolerance: the logits are near 4 in size, where adjacent
# bfloat16 values lie 2^-6 apart, and three layers of bfloat16 products
# feed them: 0.06, four such steps, holds at every position whose top-4
# experts are the reference's. Where two router scores lie closer than
# the rounding of the router's input, the program picks another expert
# than the float32 reference: that position is off by one expert's
# output (up to 1.0 here), not by rounding. So: the median within 0.06,
# at most 2 of the 16 positions beyond it, none beyond 1.0.
@pytest.mark.parametrize("dtype,tol,flips,flip_tol", [
    ("float32", 2e-4, 0, 2e-4), ("bfloat16", 0.06, 2, 1.0)])
def test_engine_prefill_chunks_then_decode_match_the_reference(
        dtype, tol, flips, flip_tol):
    model = build(dtype=dtype)
    eng = ServingEngine(model, page_size=8, num_pages=32, max_slots=4,
                        prefill_chunk=8, emit_logits=True)
    assert eng.stats()["cache_kind"] == "mla_latent"
    req = GenerationRequest(IDS[:21].tolist(), max_new_tokens=16)
    eng.submit_request(req)
    eng.run_until_idle()
    g = req.generated
    assert len(g) == 16 and len(req.token_logits) == 16
    want = ref_logits(model, IDS[:21].tolist() + g, range(20, 36))
    off = np.abs(np.asarray(req.token_logits) - want[np.arange(16), g])
    assert np.median(off) <= tol
    assert (off > tol).sum() <= flips and off.max() <= flip_tol
    # the emitted token is the reference's top, or as near it
    below = want.max(-1) - want[np.arange(16), g]
    assert (below > tol).sum() <= flips and below.max() <= flip_tol


# ----------------------------------------- (c) absorbed against expanded

def test_absorbed_and_expanded_attention_agree():
    model = build()
    attn = model.layers[1].attn
    T, page = 24, 8
    x = paddle.to_tensor(np.random.default_rng(1).standard_normal(
        (1, T, 64)).astype("float32"))
    pos = paddle.to_tensor(np.arange(T, dtype="int32")[None])
    from paddle_tpu.core.tensor import Tensor
    cache = {"ragged": True,
             "pools": {"latent": Tensor(jnp.zeros((6, page, 24)))},
             "block_tables": Tensor(jnp.asarray([[3, 1, 4]], jnp.int32)),
             "row_starts": Tensor(jnp.asarray([0], jnp.int32)),
             "row_lens": Tensor(jnp.asarray([T], jnp.int32)),
             "kv_lens": Tensor(jnp.asarray([T], jnp.int32))}
    with paddle.no_grad():
        expanded = attn(x, pos).numpy()
        absorbed = attn(x, pos, cache=cache).numpy()
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=0)
    # and both wrote the same rows: the pool holds [c | k_rope]
    dense = {"latent": None}
    with paddle.no_grad():
        attn(x, pos, cache=dense)
    rows = np.asarray(cache["pools"]["latent"]._data)[[3, 1, 4]]
    np.testing.assert_allclose(rows.reshape(T, 24),
                               dense["latent"].numpy()[0], atol=1e-6)


# --------------------------------------------------------- (d) the router

def _logits(t=6, e=16, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((t, e)),
                       jnp.float32)


def test_router_scores_are_sigmoid_and_weights_renormalised():
    z = _logits()
    idx, w = sigmoid_topk_route(z, jnp.zeros(16), 4)
    s = 1 / (1 + np.exp(-np.asarray(z)))
    top = np.argsort(-s, -1)[:, :4]
    assert sorted(map(tuple, np.sort(idx, -1))) == \
        sorted(map(tuple, np.sort(top, -1)))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


def test_router_bias_selects_but_does_not_weigh():
    z = _logits()
    bias = jnp.zeros(16).at[5].set(10.0)          # expert 5 always chosen
    idx, w = sigmoid_topk_route(z, bias, 4)
    assert (np.asarray(idx) == 5).any(-1).all()
    s = 1 / (1 + np.exp(-np.asarray(z)))
    picked = np.take_along_axis(s, np.asarray(idx), -1)   # UNBIASED
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)


@pytest.mark.parametrize("norm,scaling", [(True, 2.827), (False, 1.0),
                                          (False, 2.827)])
def test_router_scaling_and_unnormalised_weights(norm, scaling):
    z = _logits()
    idx, w = sigmoid_topk_route(z, jnp.zeros(16), 4, norm, scaling)
    s = 1 / (1 + np.exp(-np.asarray(z)))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    if norm:
        picked = picked / picked.sum(-1, keepdims=True)
    np.testing.assert_allclose(w, picked * scaling, rtol=1e-6)


def test_no_token_is_dropped_at_a_skewed_load():
    """Every token picks the same four experts: a capacity mask would drop
    most of them; here each pair has a buffer row and the layer equals
    the per-token sum."""
    paddle.seed(0)
    layer = DroplessMoELayer(64, 32, 16, 4, routed_scaling_factor=2.5,
                             n_shared_experts=0)
    layer.gate_bias._data = jnp.zeros(16).at[jnp.asarray([1, 2, 9, 14])] \
        .set(10.0)
    x = np.random.default_rng(2).standard_normal((40, 64)).astype("float32")
    y, load = layer(paddle.to_tensor(x), return_load=True)
    assert load.numpy().tolist() == [160, 12, 40]
    idx, w = layer.route(jnp.asarray(x))
    w13, w2 = np.asarray(layer.w13._data), np.asarray(layer.w2._data)
    want = np.zeros_like(x)
    for t in range(40):
        for e, g in zip(np.asarray(idx)[t], np.asarray(w)[t]):
            h = x[t] @ w13[e]
            want[t] += g * ((h[:32] / (1 + np.exp(-h[:32])) * h[32:])
                            @ w2[e])
    np.testing.assert_allclose(y.numpy(), want, atol=2e-5, rtol=0)


def test_dispatch_plan_places_every_held_pair_once():
    idx = jnp.asarray(np.random.default_rng(3).integers(0, 16, (10, 4)),
                      jnp.int32)
    plan = dispatch_plan(idx, lo=4, n_held=4, tile_m=8)
    dest = np.asarray(plan["dest"])
    held = (np.asarray(idx).reshape(-1) >= 4) & \
        (np.asarray(idx).reshape(-1) < 8)
    assert (dest[~held] == plan["rows"]).all()
    assert len(set(dest[held])) == held.sum()         # no two pairs share
    tiles = dest[held] // 8
    te = np.asarray(plan["tile_expert"])
    assert (te[tiles] == np.asarray(idx).reshape(-1)[held] - 4).all()
    assert int(plan["sizes"].sum()) == held.sum()


# ---------------------------------------------- (e) the shares add up

def test_four_shares_and_the_shared_expert_once_equal_the_uncut_layer():
    paddle.seed(0)
    full = DroplessMoELayer(64, 32, 16, 4, routed_scaling_factor=2.5)
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (2, 9, 64)).astype("float32"))
    whole = full(x).numpy()
    u = x.numpy().reshape(-1, 64)
    h = u @ np.asarray(full.shared_w13._data)
    shared = ((h[:, :32] / (1 + np.exp(-h[:, :32])) * h[:, 32:])
              @ np.asarray(full.shared_w2._data)).reshape(2, 9, 64)
    routed, pairs = 0.0, 0
    for lo in range(0, 16, 4):
        part = DroplessMoELayer(64, 32, 16, 4, experts_held=(lo, lo + 4),
                                routed_scaling_factor=2.5)
        for name in ("gate_weight", "gate_bias", "shared_w13", "shared_w2"):
            getattr(part, name)._data = getattr(full, name)._data
        part.w13._data = full.w13._data[lo:lo + 4]
        part.w2._data = full.w2._data[lo:lo + 4]
        y, load = part(x, return_load=True)
        routed = routed + (y.numpy() - shared)
        pairs += int(load.numpy()[0])
    assert pairs == 2 * 9 * 4                # every pair fell to one share
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5, rtol=0)


# --------------------------------------------- (f) kernels against twins

def _ragged_case(row_lens, kv_lens, total, rows=4, page=8, pages=24,
                 width=24, heads=4, seed=0):
    rng = np.random.default_rng(seed)
    rs = np.full(rows, total, np.int32)
    rl, kl = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
    bt = np.zeros((rows, 6), np.int32)
    free, at = rng.permutation(np.arange(1, pages)), 0
    for i, (n, kv) in enumerate(zip(row_lens, kv_lens)):
        rs[i], rl[i], kl[i] = at, n, kv
        at += n
        need = -(-kv // page)
        bt[i, :need], free = free[:need], free[need:]
    pool = jnp.asarray(rng.standard_normal((pages, page, width)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((total, heads, width)), jnp.float32)
    return q, pool, jnp.asarray(rs), jnp.asarray(rl), jnp.asarray(kl), \
        jnp.asarray(bt)


@pytest.mark.parametrize("row_lens,kv_lens,total,block_q", [
    ([1], [5], 8, 1),                  # one decode row
    ([1, 1, 1], [5, 17, 8], 4, 1),     # decode rows, one past two pages
    ([8], [8], 8, 4),                  # a whole chunk
    ([6], [14], 8, 4),                 # a chunk that crosses a page
    ([1, 6, 1], [9, 14, 30], 8, 4),    # decode rows around a chunk
    ([11, 1], [27, 3], 16, 4),         # a ragged last item
])
def test_mla_kernel_interpreted_matches_its_xla_twin(row_lens, kv_lens,
                                                     total, block_q):
    args = _ragged_case(row_lens, kv_lens, total)
    want = mla.mla_ragged_attention_reference(*args, value_width=16)
    got = mla.mla_ragged_attention(*args, value_width=16, block_q=block_q,
                                   interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


def test_mla_kernel_takes_a_lane_padded_pool():
    q, pool, *meta = _ragged_case([1, 6], [9, 14], 8)
    wide = jnp.pad(pool, ((0, 0), (0, 0), (0, 8)))
    want = mla.mla_ragged_attention_reference(q, pool, *meta,
                                              value_width=16)
    for fn, kw in ((mla.mla_ragged_attention_reference, {}),
                   (mla.mla_ragged_attention, {"interpret": True,
                                               "block_q": 4})):
        got = fn(q, wide, *meta, value_width=16, scale=24 ** -0.5, **kw)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


@pytest.mark.parametrize("sizes,tile_m", [([3, 0, 9, 1], 4), ([0, 0, 0, 0], 4),
                                          ([8, 8, 8, 8], 8)])
def test_grouped_matmul_interpreted_matches_its_xla_twin(sizes, tile_m):
    rng = np.random.default_rng(0)
    tiles_per = [-(-s // tile_m) for s in sizes]
    tiles = sum(sizes) // tile_m + len(sizes)
    te = np.repeat(np.arange(len(sizes)), tiles_per)
    n_live = len(te)
    te = np.concatenate([te, np.full(tiles - n_live,
                                     te[-1] if n_live else 0)])
    x = jnp.asarray(rng.standard_normal((tiles * tile_m, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((len(sizes), 16, 24)), jnp.float32)
    args = (x, w, jnp.asarray(te, jnp.int32),
            jnp.asarray([n_live], jnp.int32), tile_m)
    want = np.asarray(gmm.moe_grouped_matmul_reference(*args))
    got = np.asarray(gmm.moe_grouped_matmul(*args, interpret=True))
    live = n_live * tile_m
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5, rtol=0)


def test_expert_layer_on_the_interpreted_kernel_matches_the_twin():
    paddle.seed(0)
    a = DroplessMoELayer(64, 32, 16, 4, experts_held=(4, 12))
    b = DroplessMoELayer(64, 32, 16, 4, experts_held=(4, 12),
                         backend="pallas_interpret")
    b.set_state_dict(a.state_dict())
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (12, 64)).astype("float32"))
    np.testing.assert_allclose(b(x).numpy(), a(x).numpy(), atol=1e-5)


# ------------------------------------------- (g) the state declaration

def test_gpt_declares_keys_and_values_and_gets_todays_pools():
    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(num_kv_heads=2))
    spec = model.cache_spec()
    assert len(spec) == 2 and spec[0].kind == "kv"
    assert spec[0].rows == {"k": (2, 16), "v": (2, 16)}
    eng = ServingEngine(model, page_size=4, num_pages=8, max_slots=2)
    assert eng.kv.k[0].shape == eng.kv.v[0].shape == (8, 4, 2, 16)
    assert eng.kv.nbytes() == 2 * 2 * 8 * 4 * 2 * 16 * 4
    stats = eng.stats()
    assert stats["num_kv_heads"] == 2 and stats["cache_kind"] == "kv"
    assert stats["cache_bytes_per_token_layer"] == [2 * 2 * 16 * 4] * 2


def test_latent_declaration_is_576_wide_and_the_pool_whole_lane_tiles():
    spec = LayerState("mla_latent", {"latent": (576,)}, jnp.bfloat16,
                      (64, 576), row_align=128)
    assert spec.bytes_per_token() == 1152
    assert spec.bytes_per_token(padded=True) == 1280
    kv = PagedKVCache([spec] * 2, num_pages=4, page_size=8)
    assert kv.pools[0]["latent"].shape == (4, 8, 640)
    rows = jnp.ones((5, 576), jnp.bfloat16)
    kv.write_rows(1, {"latent": rows}, [2], 5)
    assert kv.gather(1, [2], 5, "latent").shape == (5, 576)
    assert float(kv.pools[1]["latent"][2, :5, 576:].astype(jnp.float32)
                 .sum()) == 0.0
    model = build(kv_lora_rank=16, qk_rope_head_dim=8)
    assert model.cache_spec()[0].rows == {"latent": (24,)}
    assert model.cache_spec()[0].query == (4, 24)


def test_prefix_cache_shares_latent_pages_between_two_requests():
    model = build()
    eng = ServingEngine(model, page_size=8, num_pages=32, max_slots=4,
                        prefill_chunk=8)
    head = IDS[:24].tolist()
    first = eng.generate(head + IDS[30:35].tolist(), max_new_tokens=4)
    req = GenerationRequest(head + IDS[40:47].tolist(), max_new_tokens=4)
    eng.submit_request(req)
    eng.run_until_idle()
    assert req.prefix_hit_tokens == 24           # three whole pages
    assert eng.stats()["prefix_hit_tokens"] == 24
    seq = head + IDS[40:47].tolist() + req.generated
    want = ref_logits(model, seq, range(30, 34)).argmax(-1)
    assert req.generated == want.tolist()
    assert len(first) == 4


def test_token_pads_ladder_and_the_round_counters():
    """An explicit ladder replaces the power-of-two schedule; a traced
    round records the router's loads and the latent rows it read."""
    from paddle_tpu.observability import tracing
    model = build(experts_held=(0, 4))
    buf = tracing.start()
    try:
        eng = ServingEngine(model, page_size=8, num_pages=32, max_slots=4,
                            prefill_chunk=8, token_pads=[4, 12])
        assert eng.warm_ragged() == [4, 12]
        eng.generate(IDS[:20].tolist(), max_new_tokens=3)
        assert eng.stats()["ragged_token_pads"] == [4, 12]
        events = [e for e in buf.events if e.get("ph") == "X"]
    finally:
        tracing.stop()
    rounds = [e for e in events if e["name"] == "decode_round"]
    routes = [e for e in events if e["name"] == "moe.route"]
    assert rounds and len(routes) == len(rounds)
    assert rounds[0]["args"]["latent_rows"] == 8
    layers = routes[0]["args"]["layers"]
    assert len(layers) == 2 and all(len(x) == 3 for x in layers)
    # 8 tokens x 4 picks, a quarter of the experts held: pairs <= 32
    assert 0 < layers[0][0] <= 32 and 0 <= layers[0][1] <= 4
