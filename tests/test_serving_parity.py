"""Decode parity + load acceptance for the serving tier (ISSUE 6).

The contract that makes paged serving safe to ship: the engine's round
produces the SAME greedy tokens (and logits to float tolerance) as the
dense compiled decode of ``models/gpt.py`` — including a request whose
context spans a page boundary and one evicted + re-admitted mid-stream.
The Poisson soak rides behind ``@pytest.mark.slow``.
"""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def seeded_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    paddle.seed(1234)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _dense_greedy(model, prompt, n):
    import paddle_tpu as paddle
    ids = paddle.to_tensor(np.asarray([prompt], dtype="int64"))
    out = model.generate(ids, max_new_tokens=n, temperature=0.0)
    return out.numpy()[0, len(prompt):].tolist()


def test_paged_vs_dense_greedy_parity_with_block_boundary(seeded_model):
    """page_size=4 with an 11-token prompt + 8 new tokens: the context
    crosses THREE page boundaries mid-stream; tokens must match the
    dense compiled decode exactly and per-step decode logits must match
    the incremental dense-cache logits to tolerance."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, 256, size=11).tolist()
    n = 8
    eng = ServingEngine(seeded_model, page_size=4, num_pages=32,
                        max_slots=2)
    eng.capture_logits = []
    req = eng.submit(prompt, max_new_tokens=n)
    eng.run_until_idle()
    got = req.result(10)
    want = _dense_greedy(seeded_model, prompt, n)
    assert got == want, (got, want)
    # logits tolerance: dense eager full-context forward vs the captured
    # paged step logits at the first step, a page-boundary-crossing step
    # (position 12 = page 3's first slot) and the last step
    checks = {0, 2, len(eng.capture_logits) - 1}
    for i, (slot_map, logits) in enumerate(eng.capture_logits):
        if i not in checks:
            continue
        slot = next(s for s, rid in slot_map.items()
                    if rid == req.request_id)
        ctx = prompt + want[:i + 1]
        ids = paddle.to_tensor(np.asarray([ctx], dtype="int64"))
        dense = seeded_model(ids).numpy()[0, -1]
        np.testing.assert_allclose(logits[slot], dense, rtol=2e-3,
                                   atol=2e-4)


def test_evicted_readmitted_parity(seeded_model):
    """A request preempted mid-stream (pages freed, recompute prefill on
    re-admission) finishes with the same tokens as an uncontended run."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(1)
    p1 = rng.randint(1, 256, size=7).tolist()
    p2 = rng.randint(1, 256, size=6).tolist()
    # 5 usable pages (page 0 is scrap), page_size 4: two requests growing
    # to 15-16 tokens cannot coexist -> someone gets evicted
    eng = ServingEngine(seeded_model, page_size=4, num_pages=6,
                        max_slots=2)
    r1 = eng.submit(p1, max_new_tokens=8)
    r2 = eng.submit(p2, max_new_tokens=8)
    eng.run_until_idle()
    assert eng.scheduler.total_evictions >= 1
    assert r1.evictions + r2.evictions >= 1
    assert r1.result(10) == _dense_greedy(seeded_model, p1, 8)
    assert r2.result(10) == _dense_greedy(seeded_model, p2, 8)


def test_concurrent_requests_do_not_cross_pollute(seeded_model):
    """Three ragged-length requests decoded in ONE continuous batch each
    match their solo dense decode (block tables isolate rows)."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 256, size=ln).tolist() for ln in (3, 9, 14)]
    eng = ServingEngine(seeded_model, page_size=4, num_pages=64,
                        max_slots=4)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert r.result(10) == _dense_greedy(seeded_model, p, 6)


def test_chunked_vs_unchunked_prefill_parity_mid_page_chunk(seeded_model):
    """ISSUE 9: chunked prefill (chunk=6 on page_size=4 — every chunk
    boundary lands MID-page) decodes token-identically to the unchunked
    engine and to the dense compiled decode, for prompts that end mid-
    chunk, mid-page, and on exact chunk multiples."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(3)
    # 11 = ends mid-chunk AND mid-page, 12 = exact chunk multiple
    prompts = [rng.randint(1, 256, size=n).tolist() for n in (11, 12)]
    chunked = ServingEngine(seeded_model, page_size=4, num_pages=64,
                            max_slots=4, prefill_chunk=6,
                            prefix_cache=False, attn_backend="xla")
    reqs = [chunked.submit(p, max_new_tokens=6) for p in prompts]
    chunked.run_until_idle()
    assert chunked.stats()["prefill_chunk_tokens"] == sum(
        len(p) for p in prompts)
    # bounded-compile contract: no round outgrew every slot decoding
    # beside one chunk, so every program is a pad of that schedule
    from paddle_tpu.serving import pad_total_tokens
    pads = chunked.stats()["ragged_token_pads"]
    assert pads and max(pads) <= pad_total_tokens(4 + 6)
    for p, r in zip(prompts, reqs):
        assert r.result(10) == _dense_greedy(seeded_model, p, 6)


@pytest.mark.slow
def test_shared_prefix_parity_and_cow_divergence(seeded_model):
    """Prefix-cache hits (shared system-prompt head) must decode token-
    identically to a cold prefill, and two requests diverging after the
    shared head must not corrupt each other (page-granular COW: the
    divergent tails live in private pages)."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(4)
    head = rng.randint(1, 256, size=8).tolist()          # 2 full pages
    tail_a = head + rng.randint(1, 256, size=5).tolist()
    tail_b = head + rng.randint(1, 256, size=5).tolist()
    eng = ServingEngine(seeded_model, page_size=4, num_pages=64,
                        max_slots=2)
    ra = eng.submit(tail_a, max_new_tokens=6)
    eng.run_until_idle()                                 # A seeds the cache
    rb = eng.submit(tail_b, max_new_tokens=6)            # hit + diverge
    rc = eng.submit(tail_a, max_new_tokens=6)            # hit, same tail
    eng.run_until_idle()
    st = eng.stats()
    assert st["prefix_hits"] == 2 and rb.prefix_hit_tokens == 8
    assert ra.result(10) == _dense_greedy(seeded_model, tail_a, 6)
    assert rb.result(10) == _dense_greedy(seeded_model, tail_b, 6)
    assert rc.result(10) == ra.result(10)


@pytest.mark.slow
def test_eviction_pressure_spares_refcounted_shared_page(seeded_model):
    """Under pool pressure a refcounted shared page is never reclaimed
    out from under its live reader: the evicted victim's PRIVATE pages
    fund the senior request, the shared head survives, and both requests
    finish with dense-parity tokens."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(5)
    head = rng.randint(1, 256, size=4).tolist()          # 1 full page
    p1 = head + rng.randint(1, 256, size=3).tolist()
    p2 = head + rng.randint(1, 256, size=2).tolist()
    # 5 usable pages: two requests growing to ~15 tokens cannot coexist
    eng = ServingEngine(seeded_model, page_size=4, num_pages=6,
                        max_slots=2)
    r1 = eng.submit(p1, max_new_tokens=8)
    r2 = eng.submit(p2, max_new_tokens=8)
    eng.run_until_idle()
    assert eng.scheduler.total_evictions >= 1
    assert r1.result(10) == _dense_greedy(seeded_model, p1, 8)
    assert r2.result(10) == _dense_greedy(seeded_model, p2, 8)
    # the cumulative-queue-wait bugfix: the evicted request's recorded
    # wait covers BOTH waiting segments (pre-eviction wait included)
    evicted = r1 if r1.evictions else r2
    assert evicted.queue_wait_s > 0


def test_prefix_insert_never_indexes_unwritten_page_slot(seeded_model):
    """Regression (review finding): with prompt+1 landing exactly on a
    page boundary and max_new_tokens=1, the finishing request's first
    generated token has NO KV written (no decode step ever runs) — the
    prefix index must cover only the PROMPT's full pages, or a follow-up
    request hitting the over-indexed page would attend garbage."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(10)
    prompt = rng.randint(1, 256, size=7).tolist()   # 7 + 1 = 2 full pages
    eng = ServingEngine(seeded_model, page_size=4, num_pages=32,
                        max_slots=2, attn_backend="xla")
    first = eng.generate(prompt, max_new_tokens=1)  # finishes at prefill
    # only the prompt's single full page may be indexed — page 1 holds
    # prompt tokens 4..6 plus the UNWRITTEN slot for the generated token
    assert eng.prefix.indexed_pages() == 1
    follow = prompt + first + rng.randint(1, 256, size=3).tolist()
    r = eng.submit(follow, max_new_tokens=6)
    eng.run_until_idle()
    assert r.prefix_hit_tokens == 4                 # head page only
    assert r.result(10) == _dense_greedy(seeded_model, follow, 6)


@pytest.fixture(scope="module")
def gqa_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    paddle.seed(4321)
    m = GPTForCausalLM(gpt_tiny(num_kv_heads=2))
    m.eval()
    return m


def test_gqa_paged_vs_dense_parity(gqa_model):
    """A num_kv_heads < num_heads config serves over [*, *, KVH, Dh]
    pools with grouped-query paged attention, token-identical to its own
    dense compiled decode — including a chunked + prefix-shared run."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(6)
    prompt = rng.randint(1, 256, size=11).tolist()
    eng = ServingEngine(gqa_model, page_size=4, num_pages=32, max_slots=2,
                        prefill_chunk=6, attn_backend="xla")
    assert eng.kv.k[0].shape[2] == 2        # KVH, not H=4
    want = _dense_greedy(gqa_model, prompt, 8)
    r1 = eng.submit(prompt, max_new_tokens=8)
    eng.run_until_idle()
    r2 = eng.submit(prompt, max_new_tokens=8)   # prefix-shared twin
    eng.run_until_idle()
    assert r1.result(10) == want
    assert r2.result(10) == want
    assert eng.stats()["prefix_hits"] == 1


@pytest.mark.parametrize("chunk", [6, None], ids=["chunked", "unchunked"])
def test_ragged_mixed_rounds_match_dense_generate(seeded_model, chunk):
    """ISSUE 13 acceptance, held to the model: on mixed prefill+decode
    rounds every request's tokens are ``model.generate``'s on the same
    weights — staggered admissions so in-flight decodes share launches
    with prompt segments (chunk boundaries mid-page: chunk=6 on
    page_size=4; unchunked, whole prompts of 12, 3 and 9 tokens in one
    round), plus a prefix-cache hit on a repeated prompt."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(12)
    prompts = [rng.randint(1, 256, size=n).tolist()
               for n in (11, 12, 3, 9)]
    eng = ServingEngine(seeded_model, page_size=4, num_pages=64,
                        max_slots=4, prefill_chunk=chunk,
                        prefill_token_budget=12 if chunk else None,
                        attn_backend="xla")
    r0 = eng.submit(prompts[0], max_new_tokens=6)
    eng.step()                       # r0 mid-prefill / first token
    rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    eng.run_until_idle()
    rep = eng.submit(prompts[0], max_new_tokens=6)   # prefix hit
    eng.run_until_idle()
    assert eng.stats()["prefix_hits"] >= 1
    for p, r in zip(prompts + [prompts[0]], [r0] + rest + [rep]):
        assert r.result(10) == _dense_greedy(seeded_model, p, 6)


@pytest.mark.parametrize("kv_heads", [8, 2], ids=["mha", "gqa"])
def test_sharded_ragged_attention_parity(kv_heads):
    """KV-head sharding over a 2-device 'model' mesh reproduces the
    unsharded ragged launch (query-head groups stay with their KV head;
    metadata replicates)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.serving import (ragged_paged_attention,
                                    sharded_ragged_attention)
    rng = np.random.RandomState(13)
    H, KVH, D, P, page, maxp, R, T = 8, kv_heads, 8, 16, 4, 4, 3, 16
    q = jnp.asarray(rng.randn(T, H, D).astype("float32"))
    kp = jnp.asarray(rng.randn(P, page, KVH, D).astype("float32"))
    vp = jnp.asarray(rng.randn(P, page, KVH, D).astype("float32"))
    bt = jnp.asarray(rng.randint(1, P, size=(R, maxp)).astype("int32"))
    # a decode row, a fresh 5-token prefill, a chunk continuation at 6
    rs = jnp.asarray(np.array([0, 1, 6], np.int32))
    rl = jnp.asarray(np.array([1, 5, 3], np.int32))
    kl = jnp.asarray(np.array([7, 5, 9], np.int32))
    ref = np.asarray(ragged_paged_attention(q, kp, vp, rs, rl, kl, bt))
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    out = np.asarray(
        sharded_ragged_attention(mesh)(q, kp, vp, rs, rl, kl, bt))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_ragged_kills_bucket_matrix_on_mixed_length_workload(
        seeded_model):
    """ISSUE 13 acceptance: a mixed-length workload (one prompt per
    power-of-two length class, then pairs) is served with <= 4 programs,
    token-identical to the model — asserted via the
    serving_compiles_total counter."""
    from paddle_tpu.observability import metrics as obsm
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(14)
    burst1 = [rng.randint(1, 256, size=n).tolist()
              for n in (3, 9, 17, 33)]
    burst2 = [rng.randint(1, 256, size=n).tolist()
              for n in (4, 4, 10, 10, 18, 18)]
    reg = obsm.enable(out_dir=None, interval_s=0)
    try:
        eng = ServingEngine(
            seeded_model, page_size=4, num_pages=64, max_slots=4,
            prefix_cache=False, attn_backend="xla")
        for burst in (burst1, burst2):
            reqs = [eng.submit(p, max_new_tokens=2) for p in burst]
            eng.run_until_idle()
            for p, r in zip(burst, reqs):
                assert r.result(10) == _dense_greedy(seeded_model, p, 2)
        snap = reg.snapshot()
        st = eng.stats()
        assert snap["counters"]["serving_compiles_total"] \
            == st["distinct_programs"]
    finally:
        obsm.disable()
    assert st["distinct_programs"] <= 4       # the ragged schedule


@pytest.mark.slow
def test_ragged_mixed_length_poisson_soak(seeded_model):
    """ISSUE 13 bench-shaped acceptance: the seeded mixed-length Poisson
    soak (log-uniform prompts, decode-heavy mix) on the ragged chunked
    engine — everything completes, the bounded-compile contract holds
    (<= 4 distinct programs, all of them ragged pads), and the pool
    drains."""
    from paddle_tpu.serving import (ServingEngine,
                                    make_mixed_length_prompts,
                                    run_poisson_load)
    prompts, news = make_mixed_length_prompts(
        24, (3, 48), vocab=256, decode_heavy=0.6,
        max_new_tokens=(2, 8), seed=11)
    eng = ServingEngine(seeded_model, page_size=4, num_pages=64,
                        max_slots=4, prefill_chunk=8,
                        attn_backend="xla")
    eng.warm_ragged()
    eng.start()
    try:
        res = run_poisson_load(eng, qps=40.0, prompts=prompts,
                               max_new_tokens=news, seed=11,
                               timeout=300.0)
        st = eng.stats()
    finally:
        eng.close()
    assert res["requests_failed"] == 0
    assert res["requests_ok"] == 24
    assert res["tokens"] == sum(news)
    assert st["distinct_programs"] <= 4
    assert st["distinct_programs"] == len(st["ragged_token_pads"])
    assert eng.kv.allocator.used_pages == 0


@pytest.mark.slow
def test_chunked_long_prompt_bounds_itl(seeded_model):
    """Slow acceptance: a near-max-seq prompt injected mid-stream. The
    chunked engine's steady-request ITL p99 stays well below the
    unchunked engine's (which stalls a full prefill into one gap), with
    token-identical output."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(8)
    steady_p = [rng.randint(1, 256, size=5).tolist() for _ in range(2)]
    long_p = rng.randint(1, 256, size=56).tolist()

    def run(chunk):
        eng = ServingEngine(seeded_model, page_size=4, num_pages=64,
                            max_slots=4, prefill_chunk=chunk,
                            prefix_cache=False)
        try:
            eng.warm_ragged()       # no pad compiles inside a gap
            steady = [eng.submit(p, max_new_tokens=14) for p in steady_p]
            for _ in range(4):
                eng.step()
            late = eng.submit(long_p, max_new_tokens=3)
            eng.run_until_idle()
            itl = [dt for r in steady for dt in r.inter_token_s()]
            toks = [r.result(30) for r in steady] + [late.result(30)]
        finally:
            eng.close()
        return max(itl), toks

    gap_un, toks_un = run(None)
    gap_ch, toks_ch = run(8)
    assert toks_un == toks_ch
    assert gap_ch < gap_un


@pytest.mark.slow
def test_shared_prefix_poisson_soak(seeded_model):
    """Open-loop shared-system-prompt soak on the chunked + prefix
    engine: everything completes, the hit rate is real, and the pool
    drains (used_pages counts live readers only — cached pages park in
    the reclaimable LRU)."""
    from paddle_tpu.serving import ServingEngine, run_poisson_load
    eng = ServingEngine(seeded_model, page_size=4, num_pages=48,
                        max_slots=4, prefill_chunk=8)
    eng.start()
    try:
        res = run_poisson_load(eng, n_requests=24, qps=40.0,
                               prompt_len=(4, 10), max_new_tokens=6,
                               seed=9, timeout=300.0, shared_prefix=12)
        stats = eng.stats()
    finally:
        eng.close()
    assert res["requests_failed"] == 0
    assert res["requests_ok"] == 24
    assert stats["prefix_hit_rate"] > 0.5
    assert res["queue_wait_ms_p99"] is not None
    assert eng.kv.allocator.used_pages == 0
    assert eng.kv.allocator.cached_pages > 0


@pytest.mark.slow
def test_poisson_soak_background_thread(seeded_model):
    """Open-loop Poisson load against the threaded engine: everything
    completes, tail stats are sane, and the pool drains to empty."""
    from paddle_tpu.serving import ServingEngine, run_poisson_load
    eng = ServingEngine(seeded_model, page_size=4, num_pages=48,
                        max_slots=4)
    eng.start()
    try:
        res = run_poisson_load(eng, n_requests=24, qps=40.0,
                               prompt_len=(4, 16), max_new_tokens=6,
                               seed=3, timeout=300.0)
    finally:
        eng.close()
    assert res["requests_failed"] == 0
    assert res["requests_ok"] == 24
    assert res["tokens"] == 24 * 6
    assert res["tokens_per_sec"] > 0
    assert res["ttft_ms_p99"] >= res["ttft_ms_p50"] > 0
    assert res["itl_ms_p99"] >= res["itl_ms_p50"] > 0
    assert eng.kv.allocator.used_pages == 0
