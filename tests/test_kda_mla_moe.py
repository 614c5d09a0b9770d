"""The delta-rule / latent attention decoder with group-limited experts
(``models/kda_mla_moe.py``), its kernel (``ops/pallas/kda_ragged.py``) and
the state a request it forces on the serving engine (``serving/
kv_cache.py``, ``attention.py``, ``engine.py``), on the CPU at a small
size: a dense KDA layer, an expert KDA layer and an expert MLA layer,
hidden 64, 4 heads of 16, latent 16 + 8, 16 experts in 4 groups (2 kept)
top-4 and a shared one, seeded weights. The plain reference is the
benchmark's own (``benchmark/reference``), which imports nothing from the
program."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.moe import DroplessMoELayer, sigmoid_topk_route
from paddle_tpu.models import (GPTForCausalLM, KDAMLAMoEForCausalLM,
                               MLAMoEForCausalLM, gpt_tiny,
                               kda_mla_moe_tiny, mla_moe_tiny)
from paddle_tpu.ops.pallas import kda_ragged as kda
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import _message_len, _plan_parts
from paddle_tpu.serving.kv_cache import LayerState, PagedKVCache
from paddle_tpu.serving.scheduler import GenerationRequest

from benchmark import flops_kda
from benchmark.models import kda_mla_moe as family

IDS = np.random.default_rng(0).integers(0, 256, size=128)


def build(seed=3, **kw):
    paddle.seed(seed)
    model = KDAMLAMoEForCausalLM(kda_mla_moe_tiny(**kw))
    model.eval()
    return model


def engine(model, num_pages=64, **kw):
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_slots", 4)
    return ServingEngine(model, page_size=4, num_pages=num_pages,
                         prefix_cache=False, **kw)


def ref_logits(model, seq, where):
    return np.asarray(family.reference.logits_at(
        family.reference_weights(model), np.asarray(seq), list(where)))


# ------------------------------------------------ (a) the model's forward

# float32 on both sides and the same equations: what is left is the order
# of the sums, a few units in the last place of logits near 4
def test_full_forward_matches_the_reference():
    model = build()
    ids = IDS[:40]
    got = model(paddle.to_tensor(ids[None].astype("int64"))).numpy()[0]
    want = ref_logits(model, ids, range(40))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_generate_is_the_reference_argmax():
    """The dense caches (a state and a convolution tail a KDA layer, latent
    rows the MLA layer) carry a request from its prompt through 10 tokens."""
    model = build()
    out = model.generate(paddle.to_tensor(IDS[None, :20].astype("int64")),
                         max_new_tokens=10).numpy()[0]
    want = ref_logits(model, out, range(19, 29))
    assert (want.argmax(-1) == out[20:]).all()


def test_a_kda_layer_knows_no_position_and_forgets_by_its_decay():
    """No positional encoding: the recurrence from a zero state gives the
    same output wherever the tokens stand; and a state scanned in two
    parts is the state scanned in one."""
    from paddle_tpu.models.kda_mla_moe import delta_rule_scan
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 12, 2, 8)), jnp.float32)
               for _ in range(3))
    alpha = jnp.asarray(rng.uniform(0.1, 1.0, (1, 12, 2, 8)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (1, 12, 2)), jnp.float32)
    zero = jnp.zeros((1, 2, 8, 8))
    o, s = delta_rule_scan(q, k, v, alpha, beta, zero)
    o1, s1 = delta_rule_scan(q[:, :5], k[:, :5], v[:, :5], alpha[:, :5],
                             beta[:, :5], zero)
    o2, s2 = delta_rule_scan(q[:, 5:], k[:, 5:], v[:, 5:], alpha[:, 5:],
                             beta[:, 5:], s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o, atol=1e-6)
    np.testing.assert_allclose(s2, s, atol=1e-6)
    # the update is the delta rule: with alpha 1 and beta 1 a key reads
    # back the value just written
    one = jnp.ones_like(alpha[:, :1])
    kn = k[:, :1] / jnp.linalg.norm(k[:, :1], axis=-1, keepdims=True)
    o3, _ = delta_rule_scan(kn, kn, v[:, :1], one, jnp.ones((1, 1, 2)), s)
    np.testing.assert_allclose(o3, v[:, :1], atol=1e-5)


# ------------------------------------- (b) through the engine, poisoned

def _poisoned(eng):
    """Make everything that holds nothing of a live request hold no
    number: before every round the scrap slot and every slot no request
    is in; a page when it is freed. A page handed out again starts at
    zero. (Not the scrap PAGE: the latent attention's XLA twin, which the
    CPU runs, gathers a row's whole block table and weighs the padded
    entries by zero, and zero times no number is no number.)"""
    def fill_pages(group, pages, value):
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
            fill_pages(group, list(pages), jnp.nan)
            free(pages)

        def taking(n, group=group, take=take):
            pages = take(n)
            fill_pages(group, pages, 0.0)
            return pages

        alloc.free, alloc.alloc = freeing, taking

    def run(steps=None):
        n = 0
        while eng.scheduler.has_work() and (steps is None or n < steps):
            live = {r.slot + 1 for r in eng.scheduler.active.values()}
            idle = jnp.asarray([s for s in range(eng.max_slots + 1)
                                if s not in live], jnp.int32)
            for l in eng.kv.state_layers:
                for name, pool in eng.kv.pools[l].items():
                    eng.kv.pools[l][name] = pool.at[idle].set(jnp.nan)
            eng.step()
            n += 1
    return run


# float32 keeps the 2e-4 of the plain forward at every position. bfloat16
# has tests/test_mla_moe.py's tolerance: logits near 4 in size, where
# adjacent bfloat16 values lie 2^-6 apart, fed by three layers of bfloat16
# products: 0.06 holds where the four experts are the reference's; where
# two router scores lie within the rounding of the router's input another
# expert is picked and the position is off by one expert's output.
@pytest.mark.parametrize("backend,dtype,tol,flips,flip_tol", [
    ("xla", "float32", 2e-4, 0, 2e-4), ("xla", "bfloat16", 0.06, 3, 1.0),
    ("pallas_interpret", "float32", 2e-4, 0, 2e-4)])
def test_engine_prefill_chunks_then_decode_match_the_reference(
        backend, dtype, tol, flips, flip_tol):
    """Chunks of 8 and then a token a round, three requests of different
    lengths in one round, the third in the slot the first left: its state
    starts from zero and nothing of the first's leaks. (The kernel is
    float32 whatever the model's dtype: interpreted once.)"""
    model = build(dtype=dtype)
    eng = engine(model, emit_logits=True, max_slots=2)
    if backend == "pallas_interpret":
        eng._state_impl = lambda *a: kda.kda_ragged(*a, interpret=True)
        eng._ragged_fn = eng._build_round()
    assert eng.stats()["cache_kind"] == "mla_latent"
    assert [g.name for g in eng.kv.groups] == ["mla_latent"]
    assert eng.kv.state_layers == [0, 1]
    run = _poisoned(eng)
    first = GenerationRequest(IDS[80:93].tolist(), max_new_tokens=5)
    req = GenerationRequest(IDS[:37].tolist(), max_new_tokens=24)
    # waits for a slot: the first's, when it ends
    third = GenerationRequest(IDS[100:111].tolist(), max_new_tokens=6)
    for r in (first, req, third):
        eng.submit_request(r)
    run()
    assert first.slot is None and len(third.generated) == 6
    for r, p in ((req, IDS[:37]), (first, IDS[80:93]),
                 (third, IDS[100:111])):
        g, n = r.generated, len(r.generated)
        assert np.isfinite(r.token_logits).all() and len(r.token_logits) == n
        want = ref_logits(model, p.tolist() + g,
                          range(len(p) - 1, len(p) + n - 1))
        off = np.abs(np.asarray(r.token_logits) - want[np.arange(n), g])
        assert np.median(off) <= tol
        assert (off > tol).sum() <= flips and off.max() <= flip_tol
    assert eng.kv.allocator.used_pages == 0


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_two_requests_chunks_in_one_round_match_the_reference(backend):
    """``prefill_token_budget`` of two chunks, as the benchmark's cell
    runs: a round carries a chunk of each of the two requests at the head
    of the prefill queue beside the decode rows; the third waits its
    turn. Every request's logits are the reference's."""
    model = build(dtype="float32")
    eng = engine(model, emit_logits=True, max_slots=3,
                 prefill_token_budget=16)
    if backend == "pallas_interpret":
        eng._state_impl = lambda *a: kda.kda_ragged(*a, interpret=True)
        eng._ragged_fn = eng._build_round()
    run = _poisoned(eng)
    prompts = (IDS[:37], IDS[40:69], IDS[80:93])
    reqs = [GenerationRequest(p.tolist(), max_new_tokens=n)
            for p, n in zip(prompts, (6, 9, 4))]
    for r in reqs:
        eng.submit_request(r)
    run(steps=1)
    assert [r.num_cached for r in reqs] == [8, 8, 0]
    run(steps=3)
    # the second prompt's last chunk (5 tokens) beside the first's fourth
    assert [r.num_cached for r in reqs] == [32, 29, 0]
    assert len(reqs[1].generated) == 1
    run()
    for r, p in zip(reqs, prompts):
        g, n = r.generated, len(r.generated)
        assert np.isfinite(r.token_logits).all() and len(r.token_logits) == n
        want = ref_logits(model, p.tolist() + g,
                          range(len(p) - 1, len(p) + n - 1))
        off = np.abs(np.asarray(r.token_logits) - want[np.arange(n), g])
        assert off.max() <= 2e-4
    assert eng.kv.allocator.used_pages == 0


def test_the_state_pool_is_the_references_float32_state():
    """What the benchmark's logits cannot tell (a bfloat16 state beside a
    bfloat16 program: PERF.md section 6, PR 35) the pool itself can: after
    chunks of 8 and some decode rounds, two live requests of different
    lengths hold in their slots the reference's float32 state of their
    own tokens, to the order of the sums; the reference with its state
    rounded to bfloat16 after every token lies a hundred times as far."""
    model = build()
    eng = engine(model, max_slots=2)
    reqs = [GenerationRequest(IDS[:37].tolist(), max_new_tokens=30),
            GenerationRequest(IDS[60:71].tolist(), max_new_tokens=30)]
    for r in reqs:
        eng.submit_request(r)
    while min(len(r.generated) for r in reqs) < 6:
        eng.step()
    weights = family.reference_weights(model)
    for r in reqs:
        # the token just emitted has not been fed yet
        seen = np.asarray(r.prompt_ids + r.generated[:-1])
        want, rounded = [], []
        family.reference.hidden(weights, seen, states=want)
        family.reference.hidden(weights, seen, "bfloat16", states=rounded)
        assert len(want) == len(eng.kv.state_layers) == 2
        for l, w, b in zip(eng.kv.state_layers, want, rounded):
            pool = eng.kv.pools[l]["state"]
            assert pool.dtype == jnp.float32
            # pool index 0 is the scrap slot
            off = np.abs(np.asarray(pool[r.slot + 1]) - np.asarray(w)).max()
            low = np.abs(np.asarray(b, np.float32) - np.asarray(w)).max()
            assert off <= 2e-6 and low >= 100 * max(off, 1e-7), (off, low)


def test_an_evicted_request_recomputes_its_state_from_its_first_token():
    """The pool is too small for three long requests at once: the youngest
    is evicted, comes back into whatever slot is free, starts from a zero
    state and ends on the same tokens."""
    model = build()
    prompts = [IDS[i:i + 40].tolist() for i in (0, 20, 50)]
    roomy = engine(model, num_pages=128)
    want = [roomy.generate(p, max_new_tokens=20) for p in prompts]
    eng = engine(model, num_pages=34)
    run = _poisoned(eng)
    reqs = [GenerationRequest(p, max_new_tokens=20) for p in prompts]
    for r in reqs:
        eng.submit_request(r)
    run()
    assert eng.stats()["evictions"] >= 1
    assert [r.generated for r in reqs] == want
    assert eng.kv.allocator.used_pages == 0


def test_the_message_says_which_slot_each_row_is():
    model = build()
    eng = engine(model, max_slots=3)
    R, shape = 3, eng._bt_shape()
    assert _message_len(16, R, shape, True) == _message_len(16, R, shape) + R
    seen = []
    fn = eng._ragged_fn

    def spy(arrays, message, pools):
        parts = _plan_parts(np.asarray(message), R, shape, True)
        seen.append([p.copy() for p in parts])
        return fn(arrays, message, pools)

    eng._ragged_fn = spy
    a = GenerationRequest(IDS[:10].tolist(), max_new_tokens=3)
    b = GenerationRequest(IDS[10:15].tolist(), max_new_tokens=3)
    eng.submit_request(a)
    eng.submit_request(b)
    eng.step()
    tokens, rs, rl, kl, slots, bt = seen[0]
    # one chunk of a round: the oldest request's; its slot past the scrap
    assert rl.tolist() == [8, 0, 0] and slots.tolist() == [a.slot + 1, 0, 0]
    assert bt.shape == (3, eng.max_pages)
    eng.run_until_idle()
    # every launched row names a slot past the scrap slot; decode rows come
    # in slot order
    for _, _, rl, _, slots, _ in seen:
        assert all((s > 0) == (n > 0) for s, n in zip(slots, rl))
        if rl.max() == 1:
            used = slots[slots > 0].tolist()
            assert used == sorted(used) and len(set(used)) == len(used)


# --------------------------------------------- (c) kernel against its twin

def _case(rows, total, heads=4, dim=16, slots=5, seed=0):
    """rows: (tokens, context after them, slot) a launched row."""
    rng = np.random.default_rng(seed)

    def n(*s):
        return rng.standard_normal(s).astype(np.float32)

    q, k, v = n(total, heads, dim), n(total, heads, dim), n(total, heads, dim)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dim)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    alpha = np.exp(-5 / (1 + np.exp(-n(total, heads, dim))))
    beta = 1 / (1 + np.exp(-n(total, heads)))
    state = n(slots + 1, heads, dim, dim)
    R = 8
    rs, rl, kl, sl = (np.full(R, total, np.int32), np.zeros(R, np.int32),
                      np.zeros(R, np.int32), np.zeros(R, np.int32))
    at = 0
    for i, (ln, kv, s) in enumerate(rows):
        rs[i], rl[i], kl[i], sl[i] = at, ln, kv, s
        at += ln
    return tuple(jnp.asarray(a, jnp.float32) for a in
                 (q, k, v, alpha, beta, state)) \
        + tuple(jnp.asarray(a) for a in (sl, rs, rl, kl))


DECODE = [(1, 5, 3), (1, 1, 1), (1, 9, 4)]
CHUNKS = [(10, 10, 2), (20, 31, 5)]
MIXED = [(1, 5, 3), (1, 7, 2), (20, 20, 1), (33, 50, 4)]


def _recurrence64(args):
    """The recurrence written out in float64, a row at a time: a row at
    the start of its context starts from zero whatever its slot held.
    -> (o [T, H, D], {slot: state after the row})."""
    q, k, v, alpha, beta, state = (np.asarray(a, np.float64)
                                   for a in args[:6])
    sl, rs, rl, kl = (np.asarray(a) for a in args[6:])
    o, new = np.zeros(q.shape), {}
    for slot, t0, n, ctx in zip(sl, rs, rl, kl):
        if n == 0:
            continue
        S = np.zeros(state.shape[1:]) if ctx == n else state[slot].copy()
        for t in range(t0, t0 + n):
            S = S * alpha[t][:, :, None]
            S = S + beta[t][:, None, None] * k[t][:, :, None] \
                * (v[t] - np.einsum("hk,hkv->hv", k[t], S))[:, None, :]
            o[t] = np.einsum("hk,hkv->hv", q[t], S)
        new[int(slot)] = S
    return o, new


def _poison(args, rows):
    """Every slot no row names (the scrap slot too) holds no number."""
    named = sorted(s for _, _, s in rows)
    poisoned = np.full(args[5].shape, np.nan, np.float32)
    poisoned[named] = np.asarray(args[5])[named]
    return args[:5] + (jnp.asarray(poisoned),) + args[6:], named


# how far the chunked form may lie from the float64 recurrence, in units of
# the float32 twin's own distance on the same case (floored at the
# rounding of one float32 sum of the case's size)
CHUNKED_OVER_TWIN = 4.0


def _held_to_float64(args, rows, got_o, got_s):
    """Decode rows at the twin's rounding (1e-6: the token form, the same
    sums in the same order); chunk rows within ``CHUNKED_OVER_TWIN`` times
    the twin's own error against the recurrence in float64."""
    o64, s64 = _recurrence64(args)
    o1, s1 = kda.kda_ragged_reference(*args)
    got_o, got_s, o1, s1 = (np.asarray(a) for a in (got_o, got_s, o1, s1))
    assert np.isfinite(got_o).all()
    at = 0
    for n, _, slot in rows:
        o_row, s_row = got_o[at:at + n], got_s[slot]
        assert np.isfinite(s_row).all()
        if n == 1:
            np.testing.assert_allclose(o_row, o1[at:at + n], atol=1e-6,
                                       rtol=0)
            np.testing.assert_allclose(s_row, s1[slot], atol=1e-6, rtol=0)
        else:
            for got, twin, want in ((o_row, o1[at:at + n], o64[at:at + n]),
                                    (s_row, s1[slot], s64[slot])):
                floor = 4 * np.finfo(np.float32).eps * np.abs(want).max()
                room = CHUNKED_OVER_TWIN * max(np.abs(twin - want).max(),
                                               floor)
                assert np.abs(got - want).max() <= room
        at += n
    assert not got_o[at:].any()                   # pad tokens come back 0


# a launch of no more tokens than rows (``_case`` has 8 rows) that holds a
# row of several tokens: a prompt's tail chunk beside few decode rows
TAIL = [(1, 5, 3), (5, 12, 2), (1, 1, 4)]


@pytest.mark.parametrize("rows,total,block", [
    (DECODE, 8, None), (DECODE, 8, 16), (CHUNKS, 32, None), (CHUNKS, 32, 16),
    (MIXED, 64, None), (MIXED, 64, 16), (TAIL, 8, None), (TAIL, 8, 16)])
def test_kernel_interpreted_matches_its_xla_twin(rows, total, block):
    """Decode rows, chunk rows and both in one launch; every slot no row
    names (the scrap slot too) holds no number, and none comes out. A
    decode row is the twin's to 1e-6. A chunk row takes the chunked form
    (since PR 36), another order of the same sums: the twin's 1e-6 became
    ``CHUNKED_OVER_TWIN`` times the twin's own distance from the recurrence
    in float64, which is what that comparison justifies and no more
    (``_held_to_float64``). The row's length alone says which form: the
    launch's shape does not (``TAIL`` has as many tokens as rows, and its
    row of five tokens goes on from a state)."""
    args, named = _poison(_case(rows, total), rows)
    # blocks of another size than the launch would choose: ``_call``
    o2, s2 = kda.kda_ragged(*args, interpret=True) if block is None \
        else kda._call(*args, block=block, interpret=True)
    assert float(jnp.abs(o2).max()) > 0.01
    _held_to_float64(args, rows, o2, s2)


def _matrix_unit(a, b, contract=((2,), (1,))):
    """A float32 product as the chip's matrix unit takes it at the highest
    precision: each operand in three bfloat16 parts (the smallest 2^-16 of
    it), whatever falls under float32's smallest normal flushed to zero,
    six partial products summed in float32."""
    tiny = jnp.finfo(jnp.float32).tiny

    def flushed(x):
        return jnp.where(jnp.abs(x) < tiny, 0.0, x)

    def parts(x):
        out, rest = [], flushed(x)
        for _ in range(3):
            p = flushed(rest.astype(jnp.bfloat16).astype(jnp.float32))
            out.append(p)
            rest = flushed(rest - p)
        return out

    def dot(x, y):
        return jax.lax.dot_general(x, y, (contract, ((0,), (0,))),
                                   precision="highest")

    (ah, am, al), (bh, bm, bl) = parts(a), parts(b)
    return dot(ah, bh) + (dot(ah, bm) + dot(am, bh)) \
        + (dot(ah, bl) + dot(al, bh) + dot(am, bm))


def _gated(g):
    """``_case``'s rows of 80 and 70 tokens at head width 128 with the
    log-decay drawn by ``g(rng, shape)``."""
    rows = [(80, 80, 1), (70, 100, 2)]
    args = _case(rows, 152, heads=2, dim=128, seed=3)
    shape = args[3].shape
    alpha = np.exp(g(np.random.default_rng(4), shape)).astype(np.float32)
    return rows, args[:3] + (jnp.asarray(alpha),) + args[4:]


# the safe gate's log-decay is in (-5, 0) a token and channel
# (``kda_gates``, ``kda_lower_bound`` -5)
GATES = {
    "floor": lambda rng, s: np.full(s, -5.0),
    "ceiling": lambda rng, s: np.full(s, -1e-4),
    "mix": lambda rng, s: np.where(rng.random(s) < 0.5, -5.0, -1e-4),
    # the second row's whole first block (tokens 80-143) at the floor,
    # among tokens that hardly decay
    "block": lambda rng, s: np.broadcast_to(np.where(
        (np.arange(s[0]) // 80 == 1)[:, None, None], -5.0, -1e-4), s),
}

CHUNKED = {
    # (rows, padded tokens, _case's sizes, block)
    "512_from_zero": ([(512, 512, 2)], 512, {}, None),
    "continues_a_poisoned_pool": ([(40, 100, 3)], 48, {}, None),
    "partial_blocks": ([(33, 33, 1), (64, 80, 2), (65, 65, 3),
                        (100, 130, 4)], 264, {}, None),
    "partial_blocks_of_16": ([(33, 33, 1), (64, 80, 2), (65, 65, 3),
                              (100, 130, 4)], 264, {}, 16),
    "two_chunks_and_decode_rows": ([(1, 5, 3), (1, 9, 1), (70, 70, 2),
                                    (90, 120, 4), (1, 1, 5)], 168, {}, None),
    "head_width_128": ([(1, 7, 1), (100, 130, 2)], 104,
                       {"heads": 8, "dim": 128, "slots": 2}, None),
    # no more tokens than ``_case``'s 8 rows: the row's length decides,
    # not the launch's shape
    "as_many_tokens_as_rows": ([(1, 9, 1), (6, 40, 3), (1, 1, 2)], 8, {},
                               None),
    "as_many_tokens_as_rows_128": ([(3, 3, 1), (4, 20, 2), (1, 6, 3)], 8,
                                   {"heads": 8, "dim": 128, "slots": 3},
                                   None),
}


@pytest.mark.parametrize("case", list(CHUNKED) + [
    f"gates_{g}_{unit}" for g in GATES for unit in ("float32", "on_chip")
    if unit == "float32" or g in ("floor", "block")])
def test_chunk_rows_take_the_chunked_form_held_to_float64(case, monkeypatch):
    """A row of several tokens goes through the recurrence's chunked form
    (blocks of 64 tokens through the matrix unit, sub-blocks of 16): from
    zero and continuing a state, whole and partial blocks, several chunk
    rows beside decode rows, every slot no row names holding no number;
    and with the gates at their bounds (the floor of -5 on every channel
    for a whole block), every output finite. Held to the recurrence in
    float64 (``_held_to_float64``), not to the twin's rounding. The
    ``on_chip`` cases take every product as the chip's matrix unit does
    (``_matrix_unit``): there a factor near exp(-80) loses its lower
    parts, which is why a sub-block's factors meet at its middle."""
    if case in CHUNKED:
        rows, total, sizes, block = CHUNKED[case]
        args = _case(rows, total, **sizes)
    else:
        _, gate, unit = case.split("_", 2)
        rows, args = _gated(GATES[gate])
        block = None
        if unit == "on_chip":
            monkeypatch.setattr(kda, "_dot", _matrix_unit)
            kda._call.clear_cache()
    args, _ = _poison(args, rows)
    try:
        o, s = kda.kda_ragged(*args, interpret=True) if block is None \
            else kda._call(*args, block=block, interpret=True)
    finally:
        if case.endswith("on_chip"):
            kda._call.clear_cache()
    assert float(jnp.abs(o).max()) > 0.01
    _held_to_float64(args, rows, o, s)


def test_twin_is_the_recurrence_written_out():
    """A row at the start of its context starts from zero whatever its
    slot held; a later row goes on from its slot."""
    args = _case([(3, 3, 2), (2, 9, 4)], 8)
    o, new = kda.kda_ragged_reference(*args)
    o64, s64 = _recurrence64(args)
    np.testing.assert_allclose(o[:5], o64[:5], atol=1e-5)
    for slot in (2, 4):
        np.testing.assert_allclose(new[slot], s64[slot], atol=1e-5)
    np.testing.assert_array_equal(new[1], args[5][1])   # untouched slots


def test_the_benchmark_counts_the_recurrence_not_the_kernels_form():
    f, b = flops_kda.kda_ragged([1, 0, 512], 32, 128, 128)
    assert f == 7 * 32 * 128 * 128 * 513
    # two rows' states in and out (float32) and their tokens' operands
    assert b == 2 * 2 * 32 * 128 * 128 * 4 + 513 * 32 * (5 * 128 + 1) * 4


# ------------------------------------------------- (d) the expert layer

def _old_route(logits, bias, top_k, scaling):
    """PR 29's router, before it learned of groups."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32)[None, :],
                           top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * jnp.float32(scaling)


def test_one_group_of_one_routes_bit_equal_to_the_ungrouped_router():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(48) * 0.01, jnp.float32)
    idx, w = sigmoid_topk_route(logits, bias, 6, True, 2.5, 1, 1)
    idx0, w0 = _old_route(logits, bias, 6, 2.5)
    np.testing.assert_array_equal(idx, idx0)
    np.testing.assert_array_equal(w, w0)
    same = jax.jit(lambda l, b: sigmoid_topk_route(l, b, 6, True, 2.5)) \
        .lower(logits, bias).as_text()
    assert same == jax.jit(lambda l, b: sigmoid_topk_route(
        l, b, 6, True, 2.5, 1, 1)).lower(logits, bias).as_text()


def test_eight_groups_four_kept_against_a_hand_count():
    """512 experts in 8 groups of 64: a group's score is the sum of its
    two largest biased scores, the 4 best groups are kept and the 8
    largest among their 256 chosen; the weights are the unbiased scores
    over their sum, times 2.5."""
    rng = np.random.default_rng(1)
    logits = rng.standard_normal((32, 512)).astype(np.float32)
    bias = (rng.standard_normal(512) * 0.3).astype(np.float32)
    idx, w = sigmoid_topk_route(jnp.asarray(logits), jnp.asarray(bias), 8,
                                True, 2.5, 8, 4)
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    pick = s + bias
    outside = 0
    for t in range(32):
        groups = pick[t].reshape(8, 64)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        kept = np.argsort(-score)[:4]
        allowed = np.concatenate([np.arange(g * 64, g * 64 + 64)
                                  for g in kept])
        want = allowed[np.argsort(-pick[t, allowed])[:8]]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(want.tolist())
        chosen = np.asarray(idx[t])
        np.testing.assert_allclose(
            w[t], 2.5 * s[t, chosen] / s[t, chosen].sum(), rtol=1e-5)
        outside += len(set(np.argsort(-pick[t])[:8].tolist())
                       - set(want.tolist()))
    assert outside > 0      # the limit did change some token's experts
    # and the reference's router agrees (an identity for its weights)
    with jax.default_matmul_precision("highest"):
        ridx, rw = family.reference.route(
            jnp.asarray(logits), jnp.eye(512, dtype=jnp.float32),
            jnp.asarray(bias), 8, 8, 4, True, 2.5)
    assert (np.sort(ridx, -1) == np.sort(idx, -1)).all()
    np.testing.assert_allclose(np.sort(rw, -1), np.sort(w, -1), rtol=1e-5)


def test_sixteen_shares_and_the_shared_expert_once_equal_the_uncut_layer():
    """The deployment's cut: each of 16 expert-parallel ranks holds a
    sixteenth of the experts and routes over all of them, under the group
    limit. Their routed parts and the shared expert counted once are the
    uncut layer, which is the reference's with every expert held."""
    paddle.seed(0)
    kw = dict(routed_scaling_factor=2.5, n_group=8, topk_group=4)
    full = DroplessMoELayer(64, 32, 32, 4, **kw)
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (2, 9, 64)).astype("float32"))
    whole = full(x).numpy()
    u = x.numpy().reshape(-1, 64)
    h = u @ np.asarray(full.shared_w13._data)
    shared = ((h[:, :32] / (1 + np.exp(-h[:, :32])) * h[:, 32:])
              @ np.asarray(full.shared_w2._data)).reshape(2, 9, 64)
    routed, pairs = 0.0, 0
    for lo in range(0, 32, 2):
        part = DroplessMoELayer(64, 32, 32, 4, experts_held=(lo, lo + 2),
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
    ref = family.reference
    ffn = {"gate_w": full.gate_weight._data, "gate_b": full.gate_bias._data,
           "w13": full.w13._data, "w2": full.w2._data,
           "shared_w13": full.shared_w13._data,
           "shared_w2": full.shared_w2._data}
    key = ref._cfg_key({"rms_norm_eps": 0.0, "num_experts_per_tok": 4,
                        "n_group": 8, "topk_group": 4,
                        "norm_topk_prob": True,
                        "routed_scaling_factor": 2.5,
                        "experts_held": (0, 32)})
    # the reference norms the layer's input (a scale of one) and adds it
    got = ref._moe_ffn(jnp.asarray(u), jnp.ones(64), ffn, key)
    m = u / np.sqrt((u * u).mean(-1, keepdims=True))
    want = u + full(paddle.to_tensor(m)).numpy()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_a_group_limit_needs_whole_groups():
    with pytest.raises(ValueError, match="no group limit"):
        DroplessMoELayer(64, 32, 30, 4, n_group=8, topk_group=4)
    with pytest.raises(ValueError, match="no group limit"):
        DroplessMoELayer(64, 32, 32, 4, n_group=4, topk_group=5)


# --------------------------------------------- (e) latent attention's path

def test_mla_without_a_bottleneck_has_one_query_matrix_and_a_gate_a_head():
    model = build()
    at = model.layers[2].attn
    names = {n for n, _ in at.named_parameters()}
    assert {"q_proj", "gate_proj", "kv_a_proj", "kv_b_proj", "o_proj"} \
        <= names and not {"q_a_proj", "q_b_proj"} & names
    assert tuple(at.q_proj.shape) == (64, 4 * 24)
    assert tuple(at.gate_proj.shape) == (64, 4)
    kimi = MLAMoEForCausalLM(mla_moe_tiny()).layers[0].attn
    kn = {n for n, _ in kimi.named_parameters()}
    assert {"q_a_proj", "q_a_norm", "q_b_proj"} <= kn \
        and not {"q_proj", "gate_proj"} & kn


# ------------------------------------------------- (f) the cache manager

def test_state_layers_take_slots_and_no_pages():
    model = build()
    spec = model.cache_spec()
    assert [s.kind for s in spec] == ["kda_state", "kda_state", "mla_latent"]
    assert spec[0].per_request and spec[0].group is None
    assert spec[0].bytes_per_token() == 0
    # a head's 16 x 16 float32 state and 3 rows of [q | k | v] (float32
    # here, the model's dtype)
    assert spec[0].bytes_per_request() == 4 * 16 * 16 * 4 + 3 * 192 * 4
    eng = engine(model, num_pages=16, max_slots=5)
    pools = eng.kv.pools
    assert pools[0]["state"].shape == (6, 4, 16, 16) \
        and pools[0]["state"].dtype == jnp.float32
    assert pools[0]["conv"].shape == (6, 3, 192)
    assert pools[2]["latent"].shape == (16, 4, 24)
    assert eng.kv.group_of == [None, None, 0]
    assert eng.kv.groups[0].layers == [2]
    assert eng.kv.nbytes() == 2 * 6 * spec[0].bytes_per_request() \
        + 16 * 4 * 24 * 4
    st = eng.stats()["state"]
    assert st["layers"] == 2 and st["slots"] == 5
    assert st["bytes_per_slot"] == 2 * spec[0].bytes_per_request()
    assert st["bytes"] == 6 * st["bytes_per_slot"]
    assert st["backend"] == "xla"
    # a model without such layers reports none, and its message is today's
    gpt = ServingEngine(GPTForCausalLM(gpt_tiny()), page_size=4,
                        num_pages=16, max_slots=2)
    assert gpt.stats()["state"] is None and gpt.kv.state_layers == []
    assert len(gpt._plan(8)[1]) == 5


def test_admission_is_held_by_pages_of_the_latent_layer_alone():
    """A prompt of 30 tokens needs 8 pages of the one latent group; the
    state layers ask for none."""
    model = build()
    eng = engine(model, num_pages=9)
    req = GenerationRequest(IDS[:30].tolist(), max_new_tokens=2)
    eng.submit_request(req)
    eng.step()
    assert req.state == "prefilling" and len(req.pages) == 8
    assert req.group_pages == [req.pages]
    eng.run_until_idle()
    assert len(req.generated) == 2


def test_a_model_of_state_layers_alone_is_refused():
    spec = LayerState("kda_state", {"state": (2, 4, 4)}, jnp.float32,
                      (2, 4), per_request=True)
    with pytest.raises(ValueError, match="keeps rows a token"):
        PagedKVCache([spec], 8, 4, max_slots=2)
    with pytest.raises(ValueError, match="max_slots"):
        PagedKVCache([spec, LayerState("kv", {"k": (1, 4), "v": (1, 4)},
                                       jnp.float32, (1, 4))], 8, 4)


def test_sharing_and_migration_refuse_a_model_with_a_state():
    model = build()
    why = "cannot be re-read by position"
    with pytest.raises(ValueError, match="prefix cache.*" + why):
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


def test_the_seven_argument_round_asks_for_the_slots():
    eng = engine(build(), jit=False)
    step = eng._build_ragged_step()
    z = jnp.zeros(4, jnp.int32)
    with pytest.raises(ValueError, match="row_slots"):
        step(eng._param_arrays, jnp.zeros(8, jnp.int32), z + 8, z, z,
             jnp.zeros(eng._bt_shape(), jnp.int32), eng.kv.pools)


# ------------------------------------------------------------ the tracing

def test_a_traced_round_says_how_many_states_it_reads():
    from paddle_tpu.observability import tracing
    model = build(experts_held=(0, 4))
    buf = tracing.start()
    try:
        eng = engine(model, token_pads=[4, 12])
        assert eng.warm_ragged() == [4, 12]
        a = GenerationRequest(IDS[:20].tolist(), max_new_tokens=3)
        b = GenerationRequest(IDS[30:37].tolist(), max_new_tokens=4)
        eng.submit_request(a)
        eng.submit_request(b)
        eng.run_until_idle()
        events = [e for e in buf.events if e.get("ph") == "X"]
    finally:
        tracing.stop()
    rounds = [e["args"] for e in events if e["name"] == "decode_round"]
    routes = [e for e in events if e["name"] == "moe.route"]
    assert rounds and len(routes) == len(rounds)
    for r in rounds:
        rows = sum(n > 0 for n in r["row_lens"])
        assert r["state_rows"] == 2 * rows          # two state layers
        assert r["latent_rows"] == sum(r["kv_lens"])
        assert "kv_rows" not in r
    assert max(r["state_rows"] for r in rounds) == 4
    # the two expert layers report, in layer order
    assert len(routes[0]["args"]["layers"]) == 2


def test_a_traced_round_counts_the_tokens_of_its_chunk_rows():
    """``state_chunk_tokens``: the tokens of the rows longer than one
    token times the state layers. A round with one chunk row of n tokens
    over two state layers reports 2n, a decode-only round 0, and
    ``state_rows`` is what it was."""
    from paddle_tpu.observability import tracing
    model = build(experts_held=(0, 4))
    buf = tracing.start()
    try:
        eng = engine(model)
        a = GenerationRequest(IDS[:13].tolist(), max_new_tokens=3)
        b = GenerationRequest(IDS[30:31].tolist(), max_new_tokens=2)
        eng.submit_request(a)
        eng.run_until_idle()
        eng.submit_request(b)
        eng.run_until_idle()
        rounds = [e["args"] for e in buf.events
                  if e.get("ph") == "X" and e["name"] == "decode_round"]
    finally:
        tracing.stop()
    assert len(eng.kv.state_layers) == 2
    # a chunk of 8, the prompt's last 5, two decode rounds; then a prompt
    # of one token (a row of one token is no chunk row) and a decode round
    assert [r["row_lens"] for r in rounds] == [[8], [5], [1], [1], [1], [1]]
    assert [r["state_chunk_tokens"] for r in rounds] == [16, 10, 0, 0, 0, 0]
    assert [r["state_rows"] for r in rounds] == [2] * 6
