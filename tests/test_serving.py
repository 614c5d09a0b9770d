"""Serving tier units — paged KV cache, scheduler, decode backends, engine.

Fast tier-1 coverage for ``paddle_tpu/serving/`` (ISSUE 6): allocator +
pool roundtrips, the attention backend's decode rows + the A/B gate,
continuous-batching admission/eviction/backpressure, the no-decode-gap
acceptance, streaming callbacks, and the metrics-registry rows. Load/soak
runs live in test_serving_parity.py behind ``@pytest.mark.slow``.
"""
import os
import time

import numpy as np
import pytest


# --------------------------------------------------------------- buckets

def test_pick_bucket_shared_helper():
    from paddle_tpu.inference import pick_bucket
    assert pick_bucket(1, [1, 2, 4]) == 1
    assert pick_bucket(3, [1, 2, 4]) == 4
    assert pick_bucket(9, [1, 2, 4]) == 4  # clamp to the largest
    # ISSUE 13 satellite: serving launch sites that cannot split must
    # fail loudly instead of clamping down and truncating the round
    with pytest.raises(ValueError, match="largest configured bucket"):
        pick_bucket(9, [1, 2, 4], strict=True)
    assert pick_bucket(4, [1, 2, 4], strict=True) == 4


def test_ragged_token_pad_schedule():
    from paddle_tpu.serving import pad_total_tokens
    assert pad_total_tokens(1) == 8      # floor: tiny rounds share one
    assert pad_total_tokens(8) == 8
    assert pad_total_tokens(9) == 16
    assert pad_total_tokens(100) == 128
    # the whole contract: distinct programs over a lifetime are the
    # log2 of the round-size range, not a bucket-grid product
    pads = {pad_total_tokens(t) for t in range(1, 129)}
    assert pads == {8, 16, 32, 64, 128}


# ------------------------------------------------------------- allocator

def test_block_allocator_alloc_free_oom():
    from paddle_tpu.serving import BlockAllocator, OutOfPages
    a = BlockAllocator(8, reserved=1)
    assert a.capacity == 7
    p1 = a.alloc(3)
    assert len(p1) == 3 and all(p >= 1 for p in p1)  # page 0 is scrap
    assert a.used_pages == 3
    with pytest.raises(OutOfPages):
        a.alloc(5)  # all-or-nothing: only 4 free
    assert a.used_pages == 3  # failed alloc granted nothing
    a.free(p1)
    assert a.free_pages == 7 and a.occupancy_pct() == 0.0
    with pytest.raises(ValueError):
        a.free([p1[0]])  # double free
    with pytest.raises(ValueError):
        a.free([0])      # reserved page


def test_pages_for():
    from paddle_tpu.serving import pages_for
    assert pages_for(0, 4) == 0
    assert pages_for(1, 4) == 1
    assert pages_for(4, 4) == 1
    assert pages_for(5, 4) == 2


def test_block_allocator_refcounts_and_reclaimable_lru():
    """ISSUE 9: pages are refcounted (prefix sharing), free() is a deref,
    and refcount-0 pages whose content the prefix cache still indexes
    park in a reclaimable LRU the allocator drains oldest-first ONLY
    after the free list runs dry."""
    from paddle_tpu.serving import BlockAllocator, PrefixCache
    a = BlockAllocator(8, reserved=1)
    pc = PrefixCache(a, page_size=4)
    pgs = a.alloc(2)
    a.ref(pgs)                      # second reader
    a.free(pgs)                     # first reader gone: still live
    assert all(a.refcount(p) == 1 for p in pgs)
    assert a.used_pages == 2
    pc.insert(list(range(8)), pgs)  # content indexed -> reclaimable later
    a.free(pgs)                     # last reader: park, don't free
    assert a.cached_pages == 2 and a.used_pages == 0
    assert a.can_alloc(7)           # reclaimable counts as allocatable
    # free list (5 pages) drains before any cached page is reclaimed
    got = a.alloc(5)
    assert a.cached_pages == 2 and pc.indexed_pages() == 2
    # the 6th page must come from the reclaimable LRU (oldest first) and
    # its index entry — plus the child chained behind it — must drop
    more = a.alloc(1)
    assert more[0] == pgs[0]
    assert pc.indexed_pages() == 0  # parent reclaim drops the subtree
    with pytest.raises(ValueError):
        a.ref([more[0], 99])        # 99 was never allocated
    a.free(got + more)
    with pytest.raises(ValueError):
        a.free([got[0]])            # true double free still detected


def test_prefix_cache_trie_lookup_hit_cap_and_cow_boundary():
    """Chained full-page trie: a hit requires the WHOLE preceding chain
    to match (page content is prefix-dependent), divergence mid-page is
    a miss, and the hit is capped at len(prompt)-1 so the last token is
    always computed. Shared pages gain readers; the divergent tail
    allocates private pages (page-granular copy-on-write)."""
    from paddle_tpu.serving import BlockAllocator, PrefixCache
    a = BlockAllocator(16, reserved=1)
    pc = PrefixCache(a, page_size=4)
    prompt = list(range(100, 112))          # 3 full pages
    pgs = a.alloc(3)
    pc.insert(prompt, pgs)
    # identical prompt: hits 2 pages (cap leaves the last page computed
    # because 12 tokens = exactly 3 pages, (12-1)//4 = 2)
    hit, n = pc.lookup(prompt)
    assert hit == pgs[:2] and n == 8
    assert a.refcount(pgs[0]) == 2 and a.refcount(pgs[2]) == 1
    a.free(hit)
    # longer prompt with the same head: all 3 pages now shareable
    hit, n = pc.lookup(prompt + [7, 8, 9])
    assert hit == pgs and n == 12
    a.free(hit)
    # divergence INSIDE page 2 -> only the untouched head pages hit
    fork = prompt[:6] + [999] + prompt[7:]
    hit, n = pc.lookup(fork)
    assert hit == pgs[:1] and n == 4
    a.free(hit)
    # a chain starting mid-way never matches (parent link is the trie)
    hit, n = pc.lookup(prompt[4:])
    assert hit == [] and n == 0
    # clear() drops the whole index + counters but touches no refcounts
    # (bench warm-state isolation)
    pc.record(8)
    pc.clear()
    assert pc.indexed_pages() == 0 and pc.hits == 0
    assert pc.lookup(prompt) == ([], 0)
    assert a.refcount(pgs[0]) == 1     # owner's ref untouched


def test_prefix_cache_never_reclaims_live_shared_page():
    """ISSUE 9 eviction rule: pool pressure reclaims only refcount-0
    cached pages; a shared page with a live reader is spared and the
    allocator raises OutOfPages instead of stealing it."""
    from paddle_tpu.serving import BlockAllocator, OutOfPages, PrefixCache
    a = BlockAllocator(6, reserved=1)       # 5 usable
    pc = PrefixCache(a, page_size=4)
    pgs = a.alloc(2)
    pc.insert(list(range(8)), pgs)
    hit, _ = pc.lookup(list(range(8)) + [1])   # live reader on both
    a.free(pgs)                                # owner gone, reader holds
    with pytest.raises(OutOfPages):
        a.alloc(4)                             # 3 free, shared spared
    a.free(hit)                                # reader done -> reclaimable
    assert len(a.alloc(5)) == 5                # now reclaimable, LRU'd
    assert pc.indexed_pages() == 0


# ------------------------------------------------------------- KV cache

def test_paged_kv_cache_prefill_roundtrip():
    import jax.numpy as jnp
    from paddle_tpu.serving import PagedKVCache
    from paddle_tpu.serving.kv_cache import kv_state
    kv = PagedKVCache([kv_state(2, 2, 3, jnp.float32)] * 2, num_pages=8,
                      page_size=4)
    rng = np.random.RandomState(0)
    k = rng.randn(6, 2, 3).astype("float32")  # 6 tokens -> 2 pages
    v = rng.randn(6, 2, 3).astype("float32")
    pages = kv.allocator.alloc(2)
    kv.write_prefill(1, jnp.asarray(k), jnp.asarray(v), pages, 6)
    np.testing.assert_allclose(np.asarray(kv.gather(1, pages, 6, "k")), k,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(kv.gather(1, pages, 6, "v")), v,
                               rtol=1e-6)
    # layer 0 untouched
    assert float(jnp.abs(kv.k[0]).sum()) == 0.0
    with pytest.raises(ValueError):
        kv.write_prefill(0, jnp.asarray(k), jnp.asarray(v), pages[:1], 6)


# ------------------------------------------------------ attention backend

def _rand_paged_case(rng, H=4, KVH=4, B=3, Dh=8, P=8, page=4, maxp=4):
    """``B`` rows of ONE token each in the flat layout of a round (padded
    to 8 tokens) over random pools: the decode shape."""
    import jax.numpy as jnp
    T = 8
    q = jnp.asarray(rng.randn(T, H, Dh).astype("float32"))
    kp = jnp.asarray(rng.randn(P, page, KVH, Dh).astype("float32"))
    vp = jnp.asarray(rng.randn(P, page, KVH, Dh).astype("float32"))
    bt = jnp.asarray(rng.randint(1, P, size=(B, maxp)).astype("int32"))
    rs = jnp.arange(B, dtype=jnp.int32)
    rl = jnp.ones(B, jnp.int32)
    kl = jnp.asarray(np.array([3, 7, 12], dtype="int32"))
    return q, kp, vp, rs, rl, kl, bt


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_ragged_decode_rows_match_dense_softmax(kv_heads):
    """Rows of one token through the round's attention == straight dense
    softmax attention over the gathered pages (independent formulation),
    each query head against the KV head of its group; pad tokens come
    back zeroed."""
    from paddle_tpu.serving import ragged_paged_attention
    rng = np.random.RandomState(0)
    q, kp, vp, rs, rl, kl, bt = _rand_paged_case(rng, KVH=kv_heads)
    out = np.asarray(ragged_paged_attention(q, kp, vp, rs, rl, kl, bt))
    B, (_, H, Dh) = len(kl), q.shape
    group = H // kv_heads
    for b in range(B):
        ln = int(kl[b])
        ks = np.concatenate([np.asarray(kp[int(p)]) for p in bt[b]])[:ln]
        vs = np.concatenate([np.asarray(vp[int(p)]) for p in bt[b]])[:ln]
        for h in range(H):
            s = ks[:, h // group] @ np.asarray(q)[b, h] / np.sqrt(Dh)
            p = np.exp(s - s.max())
            p /= p.sum()
            np.testing.assert_allclose(out[b, h], p @ vs[:, h // group],
                                       rtol=1e-4, atol=1e-5)
    assert not out[B:].any()


def test_backend_gate_resolution(monkeypatch):
    from paddle_tpu.serving import ab_compare_ragged, resolve_backend
    monkeypatch.delenv("PADDLE_TPU_SERVING_ATTN", raising=False)
    monkeypatch.delenv("PADDLE_TPU_KERNELS", raising=False)
    assert resolve_backend() == "auto"
    assert resolve_backend("pallas") == "pallas"
    monkeypatch.setenv("PADDLE_TPU_SERVING_ATTN", "xla")
    assert resolve_backend() == "xla"
    with pytest.raises(ValueError):
        resolve_backend("cuda")
    # off-TPU the gate never picks pallas (interpret mode is not a
    # measurement) — the standing kernel rule's serving incarnation
    rng = np.random.RandomState(2)
    row = ab_compare_ragged(*_rand_paged_case(rng), repeats=2)
    assert row["backend"] == "xla"
    assert row["xla_ms"] > 0 and row["pallas_ms"] is None


# ------------------------------------------------------------- scheduler

def _mk_sched(num_pages=16, page_size=4, slots=2, max_queue=8,
              max_seq=64):
    from paddle_tpu.serving import (BlockAllocator,
                                    ContinuousBatchingScheduler)
    alloc = BlockAllocator(num_pages)
    return ContinuousBatchingScheduler(alloc, slots, page_size, max_seq,
                                       max_queue=max_queue)


def _req(n=4, **kw):
    from paddle_tpu.serving import GenerationRequest
    kw.setdefault("max_new_tokens", 4)
    return GenerationRequest(list(range(1, n + 1)), **kw)


def test_scheduler_admit_finish_recycles_slots_and_pages():
    sched = _mk_sched(slots=2)
    reqs = [_req(6) for _ in range(3)]
    for r in reqs:
        sched.submit(r)
    admitted = sched.schedule()
    assert [r.request_id for r in admitted] == \
        [reqs[0].request_id, reqs[1].request_id]  # 2 slots
    assert sched.queue_depth() == 1
    used = sched.allocator.used_pages
    assert used == 4  # 2 requests x pages_for(7 tokens, 4) = 2 each
    # finish one: slot + pages return, third request admits next round
    slot0 = admitted[0].slot
    sched.finish(admitted[0])
    assert admitted[0].slot is None
    assert sched.allocator.used_pages == used - 2
    again = sched.schedule()
    assert [r.request_id for r in again] == [reqs[2].request_id]
    assert reqs[2].slot == slot0  # recycled slot


def test_scheduler_backpressure_and_oversize():
    from paddle_tpu.serving import QueueFull
    sched = _mk_sched(max_queue=1)
    sched.submit(_req(4))
    with pytest.raises(QueueFull):
        sched.submit(_req(4), block=False)
    with pytest.raises(QueueFull):
        sched.submit(_req(4), block=True, timeout=0.05)
    with pytest.raises(ValueError):  # could never fit the pool
        sched.submit(_req(40, max_new_tokens=60))


def test_scheduler_eviction_prefers_most_recent():
    sched = _mk_sched(num_pages=5, page_size=4, slots=2)  # 4 usable pages
    a, b = _req(7, max_new_tokens=8), _req(7, max_new_tokens=8)
    sched.submit(a)
    sched.submit(b)
    got = sched.schedule()
    assert len(got) == 2 and sched.allocator.free_pages == 0
    b.t_admit = a.t_admit + 1.0  # force distinct admit order
    # senior request a fills its second page and needs a third
    a.num_cached = 8
    b.num_cached = 7
    grown, evicted = sched.ensure_decode_capacity()
    assert evicted == [b] and b.state == "waiting" and b.evictions == 1
    assert a in grown and len(a.pages) == 3
    # b re-queued at the FRONT with its context reset for recompute
    assert sched.waiting[0] is b and b.num_cached == 0


def test_scheduler_cumulative_queue_wait_across_readmissions():
    """ISSUE 9 bugfix: eviction used to reset t_enqueue and silently drop
    the pre-eviction queue time from serving_queue_wait — queue_wait_s
    now accumulates every waiting segment across re-admissions."""
    sched = _mk_sched(num_pages=5, page_size=4, slots=2)
    a, b = _req(7, max_new_tokens=8), _req(7, max_new_tokens=8)
    b.t_enqueue -= 1.0            # b waited ~1s before admission
    sched.submit(a)
    sched.submit(b)
    sched.schedule()
    w1 = b.queue_wait_s
    assert w1 >= 1.0              # first segment recorded at admission
    b.t_admit = a.t_admit + 1.0
    a.num_cached, b.num_cached = 8, 7
    _, evicted = sched.ensure_decode_capacity()
    assert evicted == [b] and b.evictions == 1
    b.t_enqueue -= 2.0            # second waiting segment ~2s
    sched.finish(a)               # pages free up
    sched.schedule()              # b re-admits
    assert b.queue_wait_s >= w1 + 2.0   # total wait, not just the tail


def test_scheduler_prefix_hit_skips_shared_head():
    """Admission through a prefix cache: the shared head's pages arrive
    by reference (num_cached covers them — no prefill compute, no page
    writes) and only the tail allocates private pages."""
    from paddle_tpu.serving import (BlockAllocator,
                                    ContinuousBatchingScheduler,
                                    PrefixCache)
    alloc = BlockAllocator(16)
    pc = PrefixCache(alloc, page_size=4)
    sched = ContinuousBatchingScheduler(alloc, 2, 4, 64, prefix_cache=pc)
    donor_pages = alloc.alloc(2)
    head = list(range(50, 58))            # 2 full pages
    pc.insert(head, donor_pages)
    req = _req(4)
    req.prompt_ids = head + [1, 2, 3]     # shared head + private tail
    sched.submit(req)
    got = sched.schedule()
    assert got == [req]
    assert req.num_cached == 8 and req.prefix_hit_tokens == 8
    assert req.pages[:2] == donor_pages
    assert all(alloc.refcount(p) == 2 for p in donor_pages)
    assert pc.hits == 1 and pc.misses == 0
    # release: shared pages deref (donor still holds), tail pages free
    sched.finish(req)
    assert all(alloc.refcount(p) == 1 for p in donor_pages)


def test_scheduler_submit_not_blocked_by_slow_prefix_lookup():
    """ISSUE 15 fix (tpu-lint LK002): a fleet SharedPrefixCache lookup is
    a store round-trip (up to its fetch timeout); schedule() used to hold
    the scheduler lock across it, stalling every submit()/queue_depth()
    caller for the duration. The lookup now runs outside the lock."""
    import threading
    from paddle_tpu.serving import (BlockAllocator,
                                    ContinuousBatchingScheduler)

    class SlowCache:
        def __init__(self):
            self.entered = threading.Event()
            self.release = threading.Event()

        def lookup(self, prompt):
            self.entered.set()
            assert self.release.wait(5.0), "test never released the cache"
            return [], 0

        def record(self, n):
            pass

    pc = SlowCache()
    sched = ContinuousBatchingScheduler(BlockAllocator(16), 2, 4, 64,
                                        prefix_cache=pc)
    sched.submit(_req(6))
    t = threading.Thread(target=sched.schedule, daemon=True)
    t.start()
    assert pc.entered.wait(2.0)
    # the engine thread is mid-"store fetch": producers must not stall
    t0 = time.perf_counter()
    sched.submit(_req(6), block=False)
    depth = sched.queue_depth()   # in-admission head still queued: 2
    elapsed = time.perf_counter() - t0
    assert depth == 2 and elapsed < 0.5, \
        f"submit stalled {elapsed:.2f}s behind the prefix lookup"
    pc.release.set()
    t.join(5.0)
    assert not t.is_alive()


def test_scheduler_admission_rechecks_head_after_unlocked_lookup():
    """The lock is dropped around the prefix lookup, so a readmission
    (eviction / migration fallback, possibly from another engine's
    thread) can jump the queue head mid-lookup — admission must re-check
    the head and admit the readmitted request first, never bypass it."""
    from paddle_tpu.serving import (BlockAllocator,
                                    ContinuousBatchingScheduler)

    first, racer = _req(6), _req(6)

    class RacingCache:
        def __init__(self):
            self.raced = False

        def lookup(self, prompt):
            if not self.raced:
                self.raced = True
                sched.readmit(racer)   # appendleft while lock is free
            return [], 0

        def record(self, n):
            pass

    sched = ContinuousBatchingScheduler(BlockAllocator(16), 2, 4, 64,
                                        prefix_cache=RacingCache())
    sched.submit(first)
    admitted = sched.schedule()
    assert [r.request_id for r in admitted] == \
        [racer.request_id, first.request_id]


def test_shared_prefix_workload_generator():
    """load.py satellite: one common system-prompt head + per-request
    tails, deterministic per seed (the hot engine and its cold twin must
    see identical prompts)."""
    from paddle_tpu.serving import make_shared_prefix_prompts
    a = make_shared_prefix_prompts(8, (4, 9), vocab=512, shared_prefix=12,
                                   seed=3)
    b = make_shared_prefix_prompts(8, (4, 9), vocab=512, shared_prefix=12,
                                   seed=3)
    assert a == b and len(a) == 8
    head = a[0][:12]
    for p in a:
        assert p[:12] == head
        assert 4 <= len(p) - 12 <= 9
    assert any(p[12:] != a[0][12:] for p in a[1:])  # tails differ


def test_make_mixed_length_prompts_deterministic_and_knobbed():
    """ISSUE 13 satellite: the ragged stress workload — seeded log-
    uniform prompt lengths, and the decode-heavy/prefill-heavy knob
    moves both the generation budget and the prompt-length mass."""
    from paddle_tpu.serving import make_mixed_length_prompts
    a, na = make_mixed_length_prompts(16, (4, 64), vocab=512,
                                      decode_heavy=0.5,
                                      max_new_tokens=(2, 12), seed=5)
    b, nb = make_mixed_length_prompts(16, (4, 64), vocab=512,
                                      decode_heavy=0.5,
                                      max_new_tokens=(2, 12), seed=5)
    assert (a, na) == (b, nb)
    assert len(a) == 16 and all(4 <= len(p) <= 64 for p in a)
    assert set(na) <= {2, 12}
    assert len({len(p) for p in a}) > 3     # genuinely mixed lengths
    dec, nd = make_mixed_length_prompts(32, (4, 64), vocab=512,
                                        decode_heavy=1.0,
                                        max_new_tokens=(2, 12), seed=5)
    pre, np_ = make_mixed_length_prompts(32, (4, 64), vocab=512,
                                         decode_heavy=0.0,
                                         max_new_tokens=(2, 12), seed=5)
    assert set(nd) == {12} and set(np_) == {2}
    mean = lambda ps: sum(len(p) for p in ps) / len(ps)  # noqa: E731
    assert mean(dec) < mean(pre)            # decode-heavy = short prompts
    with pytest.raises(ValueError):
        make_mixed_length_prompts(4, (0, 8), vocab=32)


def test_scheduler_close_fails_waiters():
    from paddle_tpu.serving import EngineClosed
    sched = _mk_sched()
    r1, r2 = _req(4), _req(4)
    sched.submit(r1)
    sched.schedule()
    sched.submit(r2)
    sched.close()
    with pytest.raises(EngineClosed):
        r1.result(timeout=1)
    with pytest.raises(EngineClosed):
        r2.result(timeout=1)
    with pytest.raises(EngineClosed):
        sched.submit(_req(4))
    assert sched.allocator.used_pages == 0


# ---------------------------------------------------------------- engine

@pytest.fixture(scope="module")
def tiny_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    paddle.seed(7)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _engine(model, **kw):
    from paddle_tpu.serving import ServingEngine
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_slots", 2)
    # pin the backend: conftest resets the gate verdict cache per test,
    # so "auto" would re-time the A/B pair for every engine here; the
    # gate itself is covered by test_backend_gate_resolution
    kw.setdefault("attn_backend", "xla")
    return ServingEngine(model, **kw)


def test_continuous_admission_no_decode_gap(tiny_model):
    """ISSUE 6 acceptance: admitting a request mid-stream never stalls
    in-flight decodes — every engine step while A is active yields A a
    token (gap between A's tokens <= 1 step), including the step that
    admits + prefills B."""
    eng = _engine(tiny_model)
    rng = np.random.RandomState(0)
    a = eng.submit(rng.randint(1, 256, 5).tolist(), max_new_tokens=8)
    eng.step()  # A prefills + first decode
    a_counts = [len(a.generated)]
    b = None
    while not a.done():
        if b is None:
            b = eng.submit(rng.randint(1, 256, 7).tolist(),
                           max_new_tokens=4)  # mid-stream join
        eng.step()
        a_counts.append(len(a.generated))
    gaps = [y - x for x, y in zip(a_counts, a_counts[1:])]
    assert all(g >= 1 for g in gaps[:-1]), (a_counts, gaps)
    eng.run_until_idle()
    assert len(b.result(10)) == 4
    assert len(a.result(10)) == 8


def test_streaming_callbacks_and_finish_order(tiny_model):
    tokens, finals = [], []
    eng = _engine(tiny_model)
    req = eng.submit([5, 6, 7], max_new_tokens=5,
                     on_token=lambda r, t, fin: (tokens.append(t),
                                                 finals.append(fin)))
    eng.run_until_idle()
    assert tokens == req.result(5)
    assert len(tokens) == 5


def test_engine_metrics_land_in_registry(tiny_model):
    from paddle_tpu.observability import metrics as obsm
    reg = obsm.enable(out_dir=None, interval_s=0)
    try:
        eng = _engine(tiny_model, registry=reg)
        eng.generate([3, 1, 4, 1, 5], max_new_tokens=4)
        snap = reg.snapshot()
        assert snap["counters"]["serving_tokens_total"] == 4
        assert snap["counters"]['serving_requests_total{status=ok}'] == 1
        assert snap["histograms"]["serving_ttft_ms"]["count"] == 1
        assert snap["histograms"]["serving_inter_token_ms"]["count"] == 3
        assert snap["histograms"]["serving_e2e_ms"]["count"] == 1
        assert "serving_kv_occupancy_pct" in snap["gauges"]
        assert snap["gauges"]["serving_active_slots"] == 0.0
    finally:
        obsm.disable()


def test_engine_background_thread_and_close(tiny_model):
    from paddle_tpu.serving import EngineClosed
    eng = _engine(tiny_model)
    eng.start()
    req = eng.submit([9, 8, 7, 6], max_new_tokens=6)
    assert len(req.result(timeout=60)) == 6
    eng.close()
    with pytest.raises(EngineClosed):
        eng.submit([1, 2], max_new_tokens=2)


def test_serve_loop_crash_fails_waiters_and_marks_unhealthy(tiny_model):
    """ISSUE satellite: an exception escaping the background serve loop
    must not leave submitted requests waiting forever — every queued +
    in-flight waiter fails with the ACTUAL error, and the engine goes
    unhealthy so later submit()s fail fast naming the crash."""
    from paddle_tpu.serving import EngineClosed
    eng = _engine(tiny_model)
    boom = RuntimeError("decode step exploded")

    def broken_schedule(*a, **k):
        raise boom

    eng.scheduler.schedule = broken_schedule
    eng.start()
    req = eng.submit([5, 4, 3], max_new_tokens=4)
    with pytest.raises(RuntimeError, match="decode step exploded"):
        req.result(timeout=30)
    # unhealthy, not silently idle: immediate fail-fast naming the crash
    t0 = time.monotonic()
    with pytest.raises(EngineClosed, match="decode step exploded"):
        eng.submit([1, 2], max_new_tokens=2)
    assert time.monotonic() - t0 < 1.0
    with pytest.raises(EngineClosed, match="unhealthy"):
        eng.step()
    eng.close()  # idempotent after a crash


def test_engine_eos_stops_early(tiny_model):
    """eos emitted by the model freezes the row and frees its slot."""
    eng = _engine(tiny_model)
    # pick the token the model actually argmaxes first so eos hits at
    # token 1 deterministically
    first = eng.generate([2, 7, 1], max_new_tokens=1)[0]
    toks = eng.generate([2, 7, 1], max_new_tokens=6, eos_token_id=first)
    assert toks == [first]
    assert eng.scheduler.allocator.used_pages == 0


def test_ragged_round_no_decode_stall_and_budget_spread(tiny_model):
    """ISSUE 13 tentpole acceptance shape, ragged cadence: with the
    single-launch round, a LONG prompt arriving mid-stream still never
    stalls an in-flight decode — every round while A is active yields A
    exactly one token, even the rounds carrying B's 40-token prompt as
    budget-bounded chunk segments of the SAME launch; and B's prefill
    really is spread over multiple rounds, never exceeding the chunk
    budget per round."""
    with pytest.raises(ValueError, match="prefill_token_budget"):
        _engine(tiny_model, prefill_token_budget=64)   # budget sans chunk
    eng = _engine(tiny_model, num_pages=48, prefill_chunk=8,
                  prefix_cache=False)
    eng.warm_ragged()
    rng = np.random.RandomState(5)
    a = eng.submit(rng.randint(1, 256, 5).tolist(), max_new_tokens=10)
    eng.step()   # A's whole 5-token prompt rides one launch: first token
    assert len(a.generated) == 1
    b = eng.submit(rng.randint(1, 256, 40).tolist(), max_new_tokens=3)
    gaps, spent_per_round, rounds_b_pending = [], [], 0
    while not a.done():
        before = len(a.generated)
        chunk_before = eng.stats()["prefill_chunk_tokens"]
        eng.step()
        gaps.append(len(a.generated) - before)
        spent_per_round.append(
            eng.stats()["prefill_chunk_tokens"] - chunk_before)
        if not b.generated:
            rounds_b_pending += 1
    # A decoded every single round (the no-stall contract of the ONE
    # ragged launch)...
    assert all(g == 1 for g in gaps[:-1]), gaps
    # ...each round's prefill share never exceeded the chunk budget...
    assert all(s <= 8 for s in spent_per_round), spent_per_round
    # ...and B's 40-token prompt was spread over >= 5 budgeted rounds
    assert rounds_b_pending >= 4
    eng.run_until_idle()
    assert len(b.result(10)) == 3
    assert eng.stats()["prefill_chunk_tokens"] >= 45  # A's 5 + B's 40
    # the compile surface: every program this test ran is a ragged pad
    st = eng.stats()
    assert st["distinct_programs"] == len(st["ragged_token_pads"])
    assert st["distinct_programs"] <= 4


def test_compile_counter_flows_through_registry(tiny_model):
    """ISSUE 13 satellite: every shape-specialized callable the engine
    installs increments serving_compiles_total and updates the
    serving_distinct_programs gauge: one program a token pad, as a
    measured number."""
    from paddle_tpu.observability import metrics as obsm
    reg = obsm.enable(out_dir=None, interval_s=0)
    try:
        eng = _engine(tiny_model, registry=reg, prefill_chunk=6)
        eng.generate([7] * 11, max_new_tokens=4)
        snap = reg.snapshot()
        st = eng.stats()
        assert st["distinct_programs"] >= 1
        assert snap["counters"]["serving_compiles_total"] \
            == st["distinct_programs"] == len(st["ragged_token_pads"])
        assert snap["gauges"]["serving_distinct_programs"] \
            == st["distinct_programs"]
        # a repeat at the same shapes installs nothing new
        eng.generate([9] * 11, max_new_tokens=4)
        snap2 = reg.snapshot()
        assert snap2["counters"]["serving_compiles_total"] \
            == snap["counters"]["serving_compiles_total"]
    finally:
        obsm.disable()


def test_ragged_backend_gate_auto_demotes_off_tpu(tiny_model,
                                                  monkeypatch):
    """ISSUE 13 acceptance: under auto resolution the ragged engine runs
    the A/B gate at its own launch shape, and off-TPU the Pallas ragged
    kernel never serves (interpret mode is not a measurement)."""
    monkeypatch.delenv("PADDLE_TPU_SERVING_ATTN", raising=False)
    monkeypatch.delenv("PADDLE_TPU_KERNELS", raising=False)
    eng = _engine(tiny_model, attn_backend=None)   # auto -> gate runs
    assert eng.attn_backend == "xla"
    assert eng.attn_ab is not None
    assert eng.attn_ab["pallas_ms"] is None
    assert "TPU" in eng.attn_ab["reason"] or "xla" in eng.attn_ab["reason"]


def test_warm_ragged_precompiles_pad_schedule(tiny_model):
    """warm_ragged compiles every pad the engine can serve up front (a
    pad first seen mid-run is one XLA compile inside a round — an ITL
    spike), touches no request state, and is idempotent."""
    eng = _engine(tiny_model, prefill_chunk=8, prefill_token_budget=8)
    pads = eng.warm_ragged()
    # max round = 2 slots decoding + 8 chunk tokens = 10 -> pads {8, 16}
    assert pads == [8, 16]
    st = eng.stats()
    assert st["distinct_programs"] == 2
    assert eng.kv.allocator.used_pages == 0
    eng.warm_ragged()
    assert eng.stats()["distinct_programs"] == 2   # idempotent
    # serving after warmup installs nothing new
    eng.generate([3, 1, 4, 1, 5], max_new_tokens=4)
    assert eng.stats()["distinct_programs"] == 2
    # review regression: budget < chunk still carries ONE whole chunk
    # per round — the default warm coverage must include that pad
    from paddle_tpu.serving import pad_total_tokens
    wide = _engine(tiny_model, max_slots=4, num_pages=64,
                   prefill_chunk=32, prefill_token_budget=8)
    pads = wide.warm_ragged()
    assert pads[-1] >= pad_total_tokens(4 + 32)
    before = wide.stats()["distinct_programs"]
    rng = np.random.RandomState(0)
    wide.generate(rng.randint(1, 250, 30).tolist(), max_new_tokens=3)
    assert wide.stats()["distinct_programs"] == before  # no mid-run compile


def test_compiled_text_reads_the_round_program(tiny_model):
    """The serving twin of StaticFunction.compiled_text(): the optimized
    HLO of the ragged round at a served token pad — what chip_smoke.py
    looks for ``tpu_custom_call`` in. It needs a round to have run, names
    the pad it was asked for, and leaves the engine serving."""
    eng = _engine(tiny_model, prefill_chunk=8, prefill_token_budget=8)
    with pytest.raises(RuntimeError, match="warm_ragged"):
        eng.compiled_text()
    eng.warm_ragged()
    small, large = eng.compiled_text(), eng.compiled_text(16)
    assert "HloModule" in small and "tpu_custom_call" not in small
    assert small != large               # one program per token pad
    assert eng.stats()["distinct_programs"] == 2    # reading installs none
    assert len(eng.generate([3, 1, 4], max_new_tokens=3)) == 3
    with pytest.raises(RuntimeError, match="un-jitted"):
        _engine(tiny_model, jit=False).compiled_text()


def test_engine_has_one_round(tiny_model, monkeypatch):
    """The engine has one round and nothing selects another: the
    constructor takes no bucket sets and no switch, and an environment
    that used to ask for the other path changes nothing — what is
    launched is the ragged program, and its tokens are the model's."""
    import inspect
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    gone = ["ragged"] + [f"prefill_{axis}_buckets"
                         for axis in ("seq", "batch")]
    params = inspect.signature(ServingEngine.__init__).parameters
    assert not set(gone) & set(params)
    for name in gone:
        with pytest.raises(TypeError):
            _engine(tiny_model, **{name: None})
    # the retired switch, spelled in two halves: a grep of the tree for
    # its name finds nothing
    monkeypatch.setenv("PADDLE_TPU_SERVING_" + "RAGGED", "0")
    eng = _engine(tiny_model, prefill_chunk=8)
    assert not set(gone) & set(vars(eng))
    prompt = np.random.RandomState(3).randint(1, 256, 13).tolist()
    got = eng.generate(prompt, max_new_tokens=5)
    st = eng.stats()
    assert "ragged" not in st
    assert st["ragged_token_pads"]
    assert st["distinct_programs"] == len(st["ragged_token_pads"])
    ids = paddle.to_tensor(np.asarray([prompt], dtype="int64"))
    want = tiny_model.generate(ids, max_new_tokens=5, temperature=0.0)
    assert got == want.numpy()[0, len(prompt):].tolist()


def test_prefix_metrics_flow_through_registry(tiny_model):
    """Hit/miss/shared-page rows land in the PR-5 registry."""
    from paddle_tpu.observability import metrics as obsm
    reg = obsm.enable(out_dir=None, interval_s=0)
    try:
        eng = _engine(tiny_model, registry=reg)
        prompt = [9] * 9        # two full pages + tail
        eng.generate(prompt, max_new_tokens=2)
        eng.generate(prompt, max_new_tokens=2)
        snap = reg.snapshot()
        assert snap["counters"]["serving_prefix_misses_total"] == 1
        assert snap["counters"]["serving_prefix_hits_total"] == 1
        assert snap["counters"]["serving_prefix_hit_tokens_total"] == 8
        assert "serving_prefix_cached_pages" in snap["gauges"]
        assert snap["histograms"]["serving_queue_wait_ms"]["count"] == 2
        assert eng.stats()["prefix_hit_rate"] == 0.5
        # a cache-LESS metrics frontend must not export the prefix
        # family (every admission would read as a miss on a cache that
        # does not exist)
        from paddle_tpu.serving import ServingMetrics
        off = ServingMetrics(registry=reg, prefix_enabled=False)
        class _FakeReq:
            t_admit, evictions, prefix_hit_tokens = 1.0, 0, 0
            queue_wait_s = 0.0
        before = reg.snapshot()["counters"].get(
            "serving_prefix_misses_total")
        off.on_admit(_FakeReq())
        after = reg.snapshot()["counters"].get(
            "serving_prefix_misses_total")
        assert before == after
    finally:
        obsm.disable()


def test_engine_sampling_request(tiny_model):
    """temperature>0 rows sample host-side from the decode logits with a
    per-request RNG (greedy rows in the same batch stay on-device)."""
    eng = _engine(tiny_model)
    t1 = eng.generate([11, 12, 13], max_new_tokens=5, temperature=0.8,
                      top_k=20)
    assert len(t1) == 5
    assert all(0 <= t < tiny_model.config.vocab_size for t in t1)


# --------------------------------------- graceful shutdown (ISSUE 10)

def test_scheduler_begin_shutdown_names_queued_keeps_inflight():
    """begin_shutdown fails only the QUEUED requests with the named
    retryable EngineShuttingDown status; in-flight ones stay active for
    the drain, and later submits raise the same named status."""
    from paddle_tpu.serving import EngineShuttingDown, QueueFull
    sched = _mk_sched()
    r1 = _req(4)
    sched.submit(r1)
    sched.schedule()                       # r1 in flight
    r2 = _req(4)
    sched.submit(r2)                       # r2 queued
    assert [r.request_id for r in sched.begin_shutdown()] \
        == [r2.request_id]
    with pytest.raises(EngineShuttingDown):
        r2.result(timeout=1)
    assert r1.state == "active"            # kept for the drain
    with pytest.raises(EngineShuttingDown):
        sched.submit(_req(4))
    # the final close fails the drain stragglers with the same status
    sched.close()
    with pytest.raises(EngineShuttingDown):
        r1.result(timeout=1)
    assert sched.allocator.used_pages == 0


def test_engine_graceful_shutdown_drains_inflight(tiny_model):
    """SIGTERM-grade drain: in-flight decodes run to completion, queued
    requests fail with EngineShuttingDown, shutdown is idempotent and
    close() afterwards is a no-op."""
    from paddle_tpu.serving import EngineShuttingDown
    eng = _engine(tiny_model)              # max_slots=2
    r1 = eng.submit([1, 2, 3], max_new_tokens=4)
    r2 = eng.submit([4, 5, 6], max_new_tokens=4)
    eng.step()                             # both admitted into slots
    r3 = eng.submit([7, 8], max_new_tokens=2)  # queued behind full slots
    out = eng.shutdown(drain_s=60.0)
    assert out["failed_queued"] == 1 and out["failed_inflight"] == 0
    assert out["drained_tokens"] > 0
    assert len(r1.result(timeout=1)) == 4
    assert len(r2.result(timeout=1)) == 4
    with pytest.raises(EngineShuttingDown):
        r3.result(timeout=1)
    with pytest.raises(EngineShuttingDown):
        eng.submit([1], max_new_tokens=1)
    assert eng.shutdown() == {"drained_tokens": 0, "failed_queued": 0,
                              "failed_inflight": 0}
    eng.close()                            # no-op after shutdown


def test_engine_shutdown_deadline_fails_inflight_and_flushes(tiny_model,
                                                             tmp_path):
    """A zero drain budget fails the in-flight request with the named
    status (naming the deadline) and still flushes the serving metrics
    JSONL before returning."""
    import json as _json
    from paddle_tpu.observability import metrics as obsm
    from paddle_tpu.serving import EngineShuttingDown
    reg = obsm.enable(out_dir=str(tmp_path), interval_s=0)
    try:
        eng = _engine(tiny_model, registry=reg)
        req = eng.submit([5, 6, 7, 8], max_new_tokens=50)
        eng.step()                         # in flight, far from done
        out = eng.shutdown(drain_s=0.0)
        assert out["failed_inflight"] == 1
        with pytest.raises(EngineShuttingDown) as ei:
            req.result(timeout=1)
        assert "drain deadline" in str(ei.value)
        files = list(tmp_path.glob("metrics.*.jsonl"))
        assert files, "shutdown must flush the metrics JSONL"
        rows = [_json.loads(l) for l in
                files[0].read_text().splitlines() if l.strip()]
        assert any("serving_requests_total" in k
                   for r in rows for k in r.get("counters", {}))
    finally:
        obsm.disable()


def test_engine_install_sigterm_drains_and_exits_75(tiny_model,
                                                    monkeypatch):
    """install_sigterm wires the training-tier preemption convention:
    SIGTERM -> graceful drain -> exit 75 (resumable), through the one
    fault.install_preemption_handler path."""
    import signal as _signal
    from paddle_tpu.distributed import fault as _fault
    exits = []
    monkeypatch.setattr(_fault.os, "_exit",
                        lambda rc: exits.append(rc))
    prev = _signal.getsignal(_signal.SIGTERM)
    try:
        eng = _engine(tiny_model)
        assert eng.install_sigterm(drain_s=30.0) is True
        req = eng.submit([3, 1, 4], max_new_tokens=3)
        eng.step()
        os.kill(os.getpid(), _signal.SIGTERM)
        deadline = time.time() + 30
        while not exits and time.time() < deadline:
            time.sleep(0.05)
        assert exits == [_fault.EXIT_PREEMPT]
        assert len(req.result(timeout=1)) == 3  # drained, not dropped
    finally:
        _signal.signal(_signal.SIGTERM, prev)
        # the flag is process-wide: left set, the next guarded fit on this
        # xdist worker exits 75 at its first preemption poll
        _fault._preempt_event.clear()
