"""The gated window / full attention decoder with experts (``models/
afmoe.py``), its kernel (``ops/pallas/windowed_ragged_attention.py``) and
the page groups it forces on the serving engine (``serving/kv_cache.py``,
``scheduler.py``, ``engine.py``), on the CPU at a small size: 1 dense + 4
expert layers (sliding x 4, full), hidden 64, 6 query heads over 2 KV
heads of 16, a window of 12 tokens, 16 experts top-4 and a shared one,
seeded weights. The plain reference is the benchmark's own
(``benchmark/reference``), which imports nothing from the program."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.moe import DroplessMoELayer
from paddle_tpu.models import (AfmoeForCausalLM, GPTForCausalLM,
                               MLAMoEForCausalLM, afmoe_tiny, gpt_tiny,
                               mla_moe_tiny)
from paddle_tpu.ops.pallas import windowed_ragged_attention as win
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import _plan_parts
from paddle_tpu.serving.kv_cache import (LayerState, OutOfPages,
                                         PagedKVCache, pages_for)
from paddle_tpu.serving.scheduler import GenerationRequest

from benchmark import flops_afmoe
from benchmark.models import afmoe as family

IDS = np.random.default_rng(0).integers(0, 256, size=128)
WINDOW, FULL = "kv_windowed.w12", "kv_windowed"


def build(seed=3, **kw):
    paddle.seed(seed)
    model = AfmoeForCausalLM(afmoe_tiny(**kw))
    model.eval()
    return model


def engine(model, window_pages=24, full_pages=64, **kw):
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(model, page_size=4, max_slots=4, prefix_cache=False,
                         num_pages={WINDOW: window_pages, FULL: full_pages},
                         **kw)


def ref_logits(model, seq, where):
    return np.asarray(family.reference.logits_at(
        family.reference_weights(model), np.asarray(seq), list(where)))


# ------------------------------------------------ (a) the model's forward

# float32 on both sides and the same equations: what is left is the order
# of the sums (blocks of queries, experts one at a time), a few units in
# the last place of logits near 4
def test_full_forward_matches_the_reference():
    model = build()
    assert model.config.layer_types == ["sliding_attention"] * 4 \
        + ["full_attention"] and not model.layers[0].is_moe
    with paddle.no_grad():
        got = model(paddle.to_tensor(IDS[None, :40].astype("int64")))
    want = ref_logits(model, IDS[:40], range(40))    # 40 > 3 windows
    np.testing.assert_allclose(got.numpy()[0], want, atol=2e-4, rtol=0)


def test_generate_is_the_reference_argmax():
    model = build()
    out = model.generate(paddle.to_tensor(IDS[None, :20].astype("int64")),
                         max_new_tokens=6).numpy()[0]
    want = ref_logits(model, out, range(19, 25)).argmax(-1)
    assert out[20:].tolist() == want.tolist()


def test_full_layers_know_no_position_and_sliding_layers_no_far_token():
    """A full layer's output at the last token does not change when the
    earlier tokens change places (no positional encoding: attention is a
    set function of them); a sliding layer's does not change when a token
    outside its window changes at all."""
    model = build()
    x = np.random.default_rng(1).standard_normal((1, 30, 64)) \
        .astype("float32")
    pos = paddle.to_tensor(np.arange(30, dtype="int32")[None])
    swapped = x.copy()
    swapped[0, [2, 9]] = x[0, [9, 2]]
    far = x.copy()
    far[0, :18] += 1.0            # the last token sees 18..29 only
    with paddle.no_grad():
        full, slide = model.layers[4].attn, model.layers[1].attn
        assert full.window is None and slide.window == 12
        a, b = (full(paddle.to_tensor(v), pos).numpy()[0, -1]
                for v in (x, swapped))
        np.testing.assert_allclose(a, b, atol=1e-5)
        a, b, c = (slide(paddle.to_tensor(v), pos).numpy()[0, -1]
                   for v in (x, far, swapped))
        np.testing.assert_allclose(a, b, atol=1e-6)
        np.testing.assert_allclose(a, c, atol=1e-6)
        far[0, 18] += 1.0         # ... and does see token 18
        assert np.abs(slide(paddle.to_tensor(far), pos).numpy()[0, -1]
                      - a).max() > 1e-4


# ------------------- (b) chunked prefill + decode through both page groups

def _poisoned(eng):
    """Make every page that holds nothing of a live request hold no
    number: the scrap page before every round, a page when it is freed;
    a page handed out again starts at zero (stale, but numbers)."""
    def fill(group, pages, value):
        if not pages:
            return
        at = jnp.asarray(np.asarray(pages, np.int32))
        for l in group.layers:
            for name, pool in eng.kv.pools[l].items():
                eng.kv.pools[l][name] = pool.at[at].set(value)

    for group in eng.kv.groups:
        alloc = group.allocator
        free, take = alloc.free, alloc.alloc

        def freeing(pages, group=group, free=free):
            fill(group, list(pages), jnp.nan)
            free(pages)

        def taking(n, group=group, take=take):
            pages = take(n)
            fill(group, pages, 0.0)
            return pages

        alloc.free, alloc.alloc = freeing, taking

    def run():
        while eng.scheduler.has_work():
            for group in eng.kv.groups:
                fill(group, [0], jnp.nan)
            eng.step()
    return run


# float32 keeps the 2e-4 of the plain forward at every position. bfloat16
# has its own tolerance, tests/test_mla_moe.py's: logits near 4 in size,
# where adjacent bfloat16 values lie 2^-6 apart, fed by five layers of
# bfloat16 products: 0.06 holds where the four experts are the
# reference's; where two router scores lie within the rounding of the
# router's input another expert is picked and the position is off by one
# expert's output (up to 1.0 here). So: the median within 0.06, at most 3
# of the 24 positions beyond it, none beyond 1.0.
@pytest.mark.parametrize("dtype,tol,flips,flip_tol", [
    ("float32", 2e-4, 0, 2e-4), ("bfloat16", 0.06, 3, 1.0)])
def test_engine_prefill_chunks_then_decode_match_the_reference(
        dtype, tol, flips, flip_tol):
    model = build(dtype=dtype)
    eng = engine(model, emit_logits=True)
    assert eng.stats()["cache_kind"] == "kv_windowed"
    assert [g.name for g in eng.kv.groups] == [WINDOW, FULL]
    run = _poisoned(eng)
    # 70 tokens in, 24 out: nearly eight windows of context
    req = GenerationRequest(IDS[:70].tolist(), max_new_tokens=24)
    short = GenerationRequest(IDS[80:87].tolist(), max_new_tokens=5)
    eng.submit_request(req)
    eng.submit_request(short)
    run()
    g = req.generated
    assert len(g) == 24 and len(req.token_logits) == 24
    assert np.isfinite(req.token_logits).all()
    want = ref_logits(model, IDS[:70].tolist() + g, range(69, 93))
    off = np.abs(np.asarray(req.token_logits) - want[np.arange(24), g])
    assert np.median(off) <= tol
    assert (off > tol).sum() <= flips and off.max() <= flip_tol
    below = want.max(-1) - want[np.arange(24), g]
    assert (below > tol).sum() <= flips and below.max() <= flip_tol
    groups = eng.stats()["page_groups"]
    # 94 tokens are 24 pages; the window layers gave most of them back
    assert groups[WINDOW]["released"] >= 18 and groups[FULL]["released"] == 0
    assert groups[WINDOW]["held"] == groups[FULL]["held"] == 0
    assert groups[FULL]["peak_held"] > groups[WINDOW]["peak_held"]


# --------------------------------------------- (c) kernel against its twin

def _ragged_case(row_lens, kv_lens, total, window, rows=4, page=8, pages=40,
                 kvh=2, group=3, dim=16, seed=0):
    """Pools whose scrap page, freed pages (those wholly before a row's
    window) and unwritten tails hold no number."""
    rng = np.random.default_rng(seed)
    rs = np.full(rows, total, np.int32)
    rl, kl = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
    bt = np.zeros((rows, 8), np.int32)
    k = rng.standard_normal((pages, page, kvh * dim)).astype(np.float32)
    v = rng.standard_normal((pages, page, kvh * dim)).astype(np.float32)
    k[0] = v[0] = np.nan
    free, at = rng.permutation(np.arange(1, pages)), 0
    for i, (n, kv) in enumerate(zip(row_lens, kv_lens)):
        rs[i], rl[i], kl[i] = at, n, kv
        at += n
        need = -(-kv // page)
        bt[i, :need], free = free[:need], free[need:]
        if window is not None:
            bt[i, :max(0, kv - n - window + 1) // page] = 0
        if kv % page:
            k[bt[i, need - 1], kv % page:] = np.nan
            v[bt[i, need - 1], kv % page:] = np.nan
    q = rng.standard_normal((total, kvh * group, dim)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, rs, rl, kl, bt))


@pytest.mark.parametrize("row_lens,kv_lens,total,window,block_q", [
    ([1], [5], 8, None, 8),                # one decode row
    ([1, 1, 1], [5, 17, 8], 8, None, 8),   # decode rows, one past two pages
    ([8], [8], 8, None, 4),                # a whole chunk
    ([1, 6, 1], [9, 14, 30], 8, None, 4),  # decode rows around a chunk
    ([11, 1], [27, 3], 16, None, 4),       # a ragged last item
    ([1], [29], 8, 12, 8),                 # a window inside two pages
    ([1, 1, 1], [5, 17, 40], 8, 12, 8),    # shorter and longer than it
    ([8], [24], 8, 8, 4),                  # the window's edge on a page's
    ([8], [25], 8, 9, 4),                  # ... and one past it
    ([6], [38], 8, 16, 4),                 # a window of two whole pages
    ([1, 6, 1], [9, 33, 30], 8, 12, 4),
    ([11, 1], [47, 3], 16, 12, 4),
    ([16], [16], 16, 12, 4),               # a prompt longer than the window
    ([16, 1], [40, 64], 32, 16, 8),
])
def test_kernel_interpreted_matches_its_xla_twin(row_lens, kv_lens, total,
                                                 window, block_q):
    args = _ragged_case(row_lens, kv_lens, total, window)
    want = win.windowed_ragged_attention_reference(*args, window=window)
    got = win.windowed_ragged_attention(*args, window=window,
                                        block_q=block_q, interpret=True)
    assert np.isfinite(np.asarray(want)).all()
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


def test_twin_is_plain_masked_attention():
    """The twin against attention written out for one row, so that twin
    and kernel do not merely share a mistake."""
    q, k, v, rs, rl, kl, bt = _ragged_case([6], [30], 8, 12)
    got = np.asarray(win.windowed_ragged_attention_reference(
        q, k, v, rs, rl, kl, bt, window=12))
    keys = np.asarray(k)[np.asarray(bt)[0]].reshape(-1, 2, 16)
    vals = np.asarray(v)[np.asarray(bt)[0]].reshape(-1, 2, 16)
    for t in range(6):
        p = 24 + t
        for h in range(6):
            see = slice(p - 11, p + 1)
            s = keys[see, h // 3] @ np.asarray(q)[t, h] / 4.0
            w = np.exp(s - s.max())
            np.testing.assert_allclose(
                got[t, h], (w / w.sum()) @ vals[see, h // 3], atol=1e-5)


def test_an_item_launches_no_page_step_outside_its_window():
    """At the published sizes: a decode row of a window layer at a 16,384
    context reads 17 pages of 256 (16 whole ones and the edge), not 65; a
    512-token chunk's item of 32 tokens one more at most; a full layer
    all of them."""
    def steps(pos0, nq, window):
        first, end = win.item_pages(jnp.int32(pos0), jnp.int32(nq), 256,
                                    window)
        return int(end) - int(first)

    assert steps(16383, 1, 4096) == 16       # the window ends on a page's
    assert steps(16384, 1, 4096) == 17
    assert steps(16384, 1, None) == 65
    assert steps(16384 - 31, 32, 4096) == 18
    assert steps(16000, 32, 4096) <= pages_for(4096 + 32, 256) + 1 == 18
    assert steps(100, 1, 4096) == 1 and steps(0, 0, 4096) == 0
    # and the first page an item reads is the first the cache manager
    # keeps for a request about to write that position
    kv = PagedKVCache([LayerState("kv_windowed", {"k": (8,)}, jnp.float32,
                                  (1, 8), window=4096)], 4, 256)
    for pos in (0, 4095, 4096, 4351, 4352, 16384):
        first, _ = win.item_pages(jnp.int32(pos), jnp.int32(1), 256, 4096)
        assert int(first) == kv.groups[0].first_live_page(pos, 256)


@pytest.mark.parametrize("n,kv,window", [
    (1, 5, None), (1, 29, 12), (8, 24, 8), (8, 25, 9), (16, 16, 12),
    (11, 47, 12), (5, 14, 12), (20, 25, 12), (3, 3, 1)])
def test_the_benchmark_counts_the_visible_rows(n, kv, window):
    visible = sum(min(p + 1, window or p + 1) for p in range(kv - n, kv))
    read = len({j for p in range(kv - n, kv)
                for j in range(max(0, p - (window or kv) + 1), p + 1)})
    ops, nbytes = flops_afmoe.windowed_ragged([n, 0], [kv, 0], 6, 2, 16,
                                              window)
    assert ops == 4 * 6 * 16 * visible
    assert nbytes == 2 * read * 2 * 16 * 2 + 2 * n * 6 * 16 * 2


# ---------------------------------------------- (d) the shares add up

def test_eight_shares_and_the_shared_expert_once_equal_the_uncut_layer():
    """The deployment's cut: each of 8 expert-parallel ranks holds an
    eighth of the experts and routes over all of them. Their routed parts
    and the shared expert counted once are the uncut layer, which is the
    reference's with every expert held."""
    paddle.seed(0)
    kw = dict(routed_scaling_factor=2.448)
    full = DroplessMoELayer(64, 32, 16, 4, **kw)
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (2, 9, 64)).astype("float32"))
    whole = full(x).numpy()
    u = x.numpy().reshape(-1, 64)
    h = u @ np.asarray(full.shared_w13._data)
    shared = ((h[:, :32] / (1 + np.exp(-h[:, :32])) * h[:, 32:])
              @ np.asarray(full.shared_w2._data)).reshape(2, 9, 64)
    routed, pairs = 0.0, 0
    for lo in range(0, 16, 2):
        part = DroplessMoELayer(64, 32, 16, 4, experts_held=(lo, lo + 2),
                                **kw)
        for name in ("gate_weight", "gate_bias", "shared_w13", "shared_w2"):
            getattr(part, name)._data = getattr(full, name)._data
        part.w13._data = full.w13._data[lo:lo + 2]
        part.w2._data = full.w2._data[lo:lo + 2]
        y, load = part(x, return_load=True)
        routed = routed + (y.numpy() - shared)
        pairs += int(load.numpy()[0])
    assert pairs == 2 * 9 * 4                # every pair fell to one share
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5, rtol=0)
    # the uncut layer is the reference's expert layer (no norms: ones)
    ref = family.reference
    ffn = {"gate_w": full.gate_weight._data, "gate_b": full.gate_bias._data,
           "w13": full.w13._data, "w2": full.w2._data,
           "shared_w13": full.shared_w13._data,
           "shared_w2": full.shared_w2._data}
    key = ref._cfg_key({"rms_norm_eps": 0.0, "num_experts_per_tok": 4,
                        "route_norm": True, "route_scale": 2.448,
                        "experts_held": (0, 16)})
    # the reference norms the layer's input and its output (scales of one)
    got = ref._moe_ffn(jnp.asarray(u), jnp.ones(64), jnp.ones(64), ffn, key)
    m = u / np.sqrt((u * u).mean(-1, keepdims=True))
    f = full(paddle.to_tensor(m)).numpy()
    want = u + f / np.sqrt((f * f).mean(-1, keepdims=True))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# ------------------------------------------------------- (e) the allocator

def _held(req, g):
    return sum(1 for p in req.group_pages[g] if p)


def test_a_window_group_holds_a_window_and_a_chunk_a_request():
    model = build()
    eng = engine(model, window_pages=24, full_pages=128)
    # window 12 + chunk 8 = 20 tokens = 5 pages, and one for the edge
    bound = pages_for(12 + 8, 4) + 1
    reqs = [GenerationRequest(IDS[i:i + n].tolist(), max_new_tokens=30)
            for i, n in ((0, 90), (5, 50), (9, 7), (40, 66))]
    for r in reqs:
        eng.submit_request(r)
    seen = 0
    while eng.scheduler.has_work():
        eng.step()
        for r in eng.scheduler.active.values():
            assert _held(r, 0) <= bound
            assert _held(r, 1) == len(r.group_pages[1])
            # what is held is the table's tail: the head was given back
            table = r.group_pages[0]
            assert all(p == 0 for p in table[:r.released[0]])
            assert all(p > 0 for p in table[r.released[0]:])
            seen = max(seen, _held(r, 0))
    assert bound - 1 <= seen <= bound    # chunks here start on a page
    assert all(len(r.generated) == 30 for r in reqs)
    assert all(g.allocator.used_pages == 0 for g in eng.kv.groups)
    stats = eng.stats()
    assert stats["evictions"] == 0
    win_g, full_g = (stats["page_groups"][n] for n in (WINDOW, FULL))
    assert win_g["peak_held"] <= 4 * bound < full_g["peak_held"]
    assert stats["kv_occupancy_peak_pct"] == pytest.approx(
        100 * max(win_g["peak_held"] / 23, full_g["peak_held"] / 127), abs=0.01)


def test_eviction_and_readmission_return_every_page_of_every_group():
    """The full group is too small for three long requests at once: the
    youngest is evicted, gives back its pages in BOTH groups, recomputes
    and ends on the same tokens."""
    model = build()
    prompts = [IDS[i:i + 40].tolist() for i in (0, 20, 50)]
    roomy = engine(model, full_pages=128)
    want = [roomy.generate(p, max_new_tokens=20) for p in prompts]
    eng = engine(model, window_pages=24, full_pages=34)
    reqs = [GenerationRequest(p, max_new_tokens=20) for p in prompts]
    for r in reqs:
        eng.submit_request(r)
    eng.run_until_idle()
    assert eng.stats()["evictions"] >= 1
    assert [r.generated for r in reqs] == want
    for g in eng.kv.groups:
        assert g.allocator.used_pages == 0
        assert g.allocator.free_pages == g.allocator.capacity


def test_admission_is_all_or_nothing_over_the_groups():
    """A prompt that fits the full group but not (yet) the window group
    waits; nothing is taken from either."""
    model = build()
    eng = engine(model, window_pages=8, full_pages=64)   # 7 usable pages
    first = GenerationRequest(IDS[:30].tolist(), max_new_tokens=4)
    second = GenerationRequest(IDS[30:60].tolist(), max_new_tokens=4)
    eng.submit_request(first)
    eng.submit_request(second)
    eng.step()
    assert first.state == "prefilling" and second.state == "waiting"
    assert second.group_pages == [[]] and second.slot is None
    win_alloc, full_alloc = (g.allocator for g in eng.kv.groups)
    assert win_alloc.used_pages == 5 and full_alloc.used_pages == 8
    eng.run_until_idle()
    assert len(first.generated) == len(second.generated) == 4
    # a request that one of the groups could never hold is refused
    for pages in ({"window_pages": 24, "full_pages": 16},
                  {"window_pages": 4, "full_pages": 64}):
        with pytest.raises(ValueError, match="could never run"):
            engine(model, **pages).submit(IDS[:30].tolist(),
                                          max_new_tokens=60)


def test_one_group_models_get_todays_pools_tables_and_program():
    """GPT and the latent decoder declare one kind and no window: one
    group, one allocator, a 2-D block table in today's order."""
    for model in (GPTForCausalLM(gpt_tiny()),
                  MLAMoEForCausalLM(mla_moe_tiny())):
        eng = ServingEngine(model, page_size=4, num_pages=32, max_slots=3,
                            prefill_chunk=8)
        assert len(eng.kv.groups) == 1 and eng.kv.groups[0].window is None
        assert eng.kv.allocator is eng.scheduler.allocator \
            is eng.kv.groups[0].allocator
        assert all(a.shape[0] == 32 for p in eng.kv.pools
                   for a in p.values())
        tables = []
        fn = eng._ragged_fn

        def spy(arrays, message, pools):
            tables.append(_plan_parts(np.asarray(message), eng.max_slots,
                                      eng._bt_shape())[4])
            return fn(arrays, message, pools)

        eng._ragged_fn = spy
        a = GenerationRequest(IDS[:10].tolist(), max_new_tokens=3)
        b = GenerationRequest(IDS[10:15].tolist(), max_new_tokens=3)
        eng.submit_request(a)
        eng.submit_request(b)
        eng.step()
        # the free list hands out 1, 2, 3, ...: 10 + 1 tokens, then 5 + 1
        assert a.pages == [1, 2, 3] and b.pages == [4, 5]
        assert a.group_pages == [a.pages] and a.released == [0]
        assert tables[0].shape == (3, eng.max_pages) \
            and tables[0].dtype == np.int32
        assert tables[0][0, :4].tolist() == [1, 2, 3, 0]
        eng.run_until_idle()
        assert eng.kv.allocator.used_pages == 0
        assert list(eng.stats()["page_groups"]) == [model.cache_spec()[0].kind]


def test_num_pages_by_group_must_name_the_models_groups():
    model = build()
    with pytest.raises(ValueError, match="page groups"):
        ServingEngine(model, page_size=4, num_pages={"kv": 8},
                      prefix_cache=False)
    eng = ServingEngine(model, page_size=4, num_pages=16, max_slots=2,
                        prefix_cache=False)       # one count: every group
    assert [g.num_pages for g in eng.kv.groups] == [16, 16]
    assert eng.kv.nbytes() == 5 * 2 * 16 * 4 * 32 * 4
    with pytest.raises(OutOfPages):
        eng.kv.groups[0].allocator.alloc(16)


# ------------------------------------------------------- (f) the refusals

def test_sharing_and_migration_refuse_a_windowed_model():
    model = build()
    why = "slid out of a window"
    with pytest.raises(ValueError, match=why):
        ServingEngine(model, page_size=4, num_pages=16)    # prefix cache on
    eng, other = engine(model), engine(model)
    req = GenerationRequest(IDS[:9].tolist(), max_new_tokens=8)
    eng.submit_request(req)
    for _ in range(4):
        eng.step()
    assert req.state == "active"
    with pytest.raises(ValueError, match="page migration.*" + why):
        eng.snapshot_kv(req)
    with pytest.raises(ValueError, match="page migration.*" + why):
        other.adopt_request(req, [], 0)
    from paddle_tpu.serving.fleet.disagg import migrate_request
    from paddle_tpu.serving.fleet.page_share import SharedPrefixCache
    with pytest.raises(ValueError, match="page migration.*" + why):
        migrate_request(eng, other, req)
    with pytest.raises(ValueError, match="page sharing.*" + why):
        SharedPrefixCache(eng.kv, 4, share=None)
    assert req.state == "active"              # and nothing was torn
    eng.run_until_idle()
    assert len(req.generated) == 8


# ------------------------------------------------------------ the tracing

def test_a_traced_round_says_what_each_group_reads_and_gives_back():
    from paddle_tpu.observability import tracing
    model = build(experts_held=(0, 4))
    buf = tracing.start()
    try:
        eng = engine(model, token_pads=[4, 12])
        assert eng.warm_ragged() == [4, 12]
        eng.generate(IDS[:30].tolist(), max_new_tokens=3)
        events = [e for e in buf.events if e.get("ph") == "X"]
    finally:
        tracing.stop()
    rounds = [e["args"] for e in events if e["name"] == "decode_round"]
    routes = [e for e in events if e["name"] == "moe.route"]
    freed = [e["args"] for e in events
             if e["name"] == "cache.window_release"]
    assert rounds and len(routes) == len(rounds) == len(freed)
    for a in rounds:
        assert a["kv_rows"] == sum(a["kv_lens"])
        assert a["window_rows"] == sum(
            min(kv, 12 + n - 1) for n, kv in zip(a["row_lens"], a["kv_lens"]))
    # the fourth chunk: 6 tokens ending at 30 read 12 + 5 rows of a window
    # layer; the pages before position 24 - 11 went back before it
    assert rounds[3]["kv_rows"] == 30 and rounds[3]["window_rows"] == 17
    assert [f["pages"] for f in freed[:4]] == [{WINDOW: 0}, {WINDOW: 0},
                                               {WINDOW: 1}, {WINDOW: 2}]
    assert freed[3]["round"] == rounds[3]["round"]
    # expert layers only, in layer order: four of the five
    layers = routes[0]["args"]["layers"]
    assert len(layers) == 4 and all(len(x) == 3 for x in layers)
