"""A serving round crosses the host-device boundary once each way (ISSUE
34): its plan goes up as ONE int32 message in one ``jax.device_put`` and
is taken apart inside the round's program; the tokens (and an
``emit_logits`` engine's logits, bit-cast beside them) come back in one
blocking ``jax.device_get``, with whatever else the round reads that time.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.jit.api import _aval
from paddle_tpu.models import (AfmoeForCausalLM, GPTForCausalLM,
                               MLAMoEForCausalLM, afmoe_tiny, gpt_tiny,
                               mla_moe_tiny)
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import _message_len, _plan_parts
from paddle_tpu.serving.scheduler import GenerationRequest

IDS = np.random.default_rng(34).integers(1, 256, size=96)

# one page group and tokens alone; two groups (stacked tables); one group
# and each token's logit beside it
FAMILIES = {
    "gpt": (GPTForCausalLM, gpt_tiny, dict(num_pages=48)),
    "afmoe-two-groups": (AfmoeForCausalLM, afmoe_tiny,
                         dict(num_pages=48, prefix_cache=False)),
    "mla-emit-logits": (MLAMoEForCausalLM, mla_moe_tiny,
                        dict(num_pages=48, emit_logits=True)),
}


@pytest.fixture(scope="module")
def models():
    out = {}
    for name, (cls, tiny, _) in FAMILIES.items():
        paddle.seed(34)
        out[name] = cls(tiny())
        out[name].eval()
    return out


def _engine(models, family, **kw):
    kw = dict(FAMILIES[family][2], **kw)
    return ServingEngine(models[family], page_size=4, max_slots=3,
                         prefill_chunk=8, attn_backend="xla", **kw)


def _dense(model, prompt, n):
    ids = paddle.to_tensor(np.asarray([prompt], dtype="int64"))
    greedy = {"temperature": 0.0} if isinstance(model, GPTForCausalLM) else {}
    out = model.generate(ids, max_new_tokens=n, **greedy)
    return out.numpy()[0, len(prompt):].tolist()


class _Watched:
    """Stands in for one array the round's program returned: counts every
    copy to the host and says whether it was made inside the round's one
    ``jax.device_get``."""

    def __init__(self, array, log):
        self.array, self.log = array, log

    def copy_to_host_async(self):
        self.array.copy_to_host_async()

    def __array__(self, *args, **kw):
        self.log["copies"].append(self.log["inside_get"])
        return np.asarray(self.array)


def _watch(eng, monkeypatch):
    """Spies on both directions of every round of ``eng``. -> the log."""
    log = {"puts": [], "gets": 0, "launches": 0, "copies": [],
           "inside_get": False, "on_device": []}
    real_put, real_get, fn = jax.device_put, jax.device_get, eng._ragged_fn

    def put(x, *args, **kw):
        log["puts"].append(x)
        return real_put(x, *args, **kw)

    def get(tree):
        log["gets"] += 1
        log["inside_get"] = True
        try:
            return real_get(tree)
        finally:
            log["inside_get"] = False

    def launch(arrays, message, pools):
        log["launches"] += 1
        # nothing rides the call as a host array (an upload of its own)
        log["on_device"].append(all(
            isinstance(a, jax.Array) for a in jax.tree_util.tree_leaves(
                (arrays, message, pools))))
        out, row_logits, pools, aux = fn(arrays, message, pools)
        watched = jax.tree_util.tree_map(
            lambda a: _Watched(a, log), (out, row_logits, aux))
        return watched[0], watched[1], pools, watched[2]

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(jax, "device_get", get)
    eng._ragged_fn = launch
    return log


# ------------------------------------- (a) one upload, one blocking fetch

@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_round_uploads_once_and_fetches_once(models, family, traced,
                                               monkeypatch):
    """Chunked prefill mixed with decode rows, through every kind of
    engine: each round makes ONE host-to-device transfer (the message)
    and ONE blocking fetch, whatever the fetch carries, and the tokens
    are ``model.generate``'s."""
    eng = _engine(models, family)
    log = _watch(eng, monkeypatch)
    spans = tracing.start() if traced else None
    try:
        prompts = [IDS[:21].tolist(), IDS[30:35].tolist(),
                   IDS[40:53].tolist()]
        reqs = [GenerationRequest(p, max_new_tokens=6) for p in prompts]
        eng.submit_request(reqs[0])
        eng.step()                     # a chunk alone, then chunks + decode
        for r in reqs[1:]:
            eng.submit_request(r)
        eng.run_until_idle()
    finally:
        if traced:
            tracing.stop()
    st = eng.stats()
    rounds = st["steps"]
    assert rounds == log["launches"] > 6
    assert st["round_uploads"] == st["round_fetches"] == rounds
    # the spies agree with the counters: one device_put a round, of the
    # round's one int32 message; one device_get a round, and no copy to
    # the host outside it
    assert len(log["puts"]) == log["gets"] == rounds
    assert all(isinstance(m, np.ndarray) and m.dtype == np.int32
               and m.ndim == 1 for m in log["puts"])
    assert all(log["on_device"])
    assert log["copies"] and all(log["copies"])
    for r, p in zip(reqs, prompts):
        assert r.generated == _dense(models[family], p, 6)
    if family == "mla-emit-logits":
        assert all(len(r.token_logits) == 6 for r in reqs)
    if traced:
        events = [e for e in spans.events if e.get("ph") == "X"]
        launch = [e["args"] for e in events if e["name"] == "round.launch"]
        fetch = [e["args"] for e in events if e["name"] == "round.fetch"]
        assert len(launch) == len(fetch) == rounds
        assert all(a["uploads"] == 1 and "fetches" not in a for a in launch)
        assert all(a["fetches"] == 1 and "uploads" not in a for a in fetch)
        # a phase's own arguments stay its own: the next carries the round
        assert all(set(e["args"]) == {"round"} for e in events
                   if e["name"] == "round.emit")
        if family != "gpt":            # the layers' reports rode the fetch
            assert sum(e["name"] == "moe.route" for e in spans.events) \
                == rounds


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_serve_loops_spans_tile_its_thread(models, family):
    """Through ``_serve_loop`` with tracing on, every kind of engine: the
    serve thread's ``decode_round`` / ``serve.idle_wait`` / ``serve.turn``
    follow one another with no hole from the first round to the last, a
    round's six phases tile it, and the round still says what it
    launched."""
    import time
    eng = _engine(models, family)
    spans = tracing.start()
    try:
        eng.start()
        time.sleep(0.03)
        reqs = [eng.submit(IDS[a:b].tolist(), max_new_tokens=4)
                for a, b in ((0, 21), (30, 35))]
        for r in reqs:
            assert len(r.result(120)) == 4
        time.sleep(0.03)
        tid = eng._thread.ident
        rounds = eng.stats()["steps"]
        eng.close()
    finally:
        tracing.stop()
    tiles = ("decode_round", "serve.idle_wait", "serve.turn")
    mine = sorted((e for e in spans.events if e["tid"] == tid
                   and (e["name"] in tiles
                        or e["name"].startswith("round."))),
                  key=lambda e: e["ts"])
    top = [e for e in mine if e["name"] in tiles]
    assert {e["name"] for e in top} == set(tiles)
    eps = 1.0                            # us: float rounding of ts + dur
    for a, b in zip(top, top[1:]):
        assert abs(b["ts"] - (a["ts"] + a["dur"])) <= eps, (a, b)
    got = [e for e in top if e["name"] == "decode_round"]
    assert [e["args"]["round"] for e in got] == list(range(rounds))
    for r in got:
        inside = [e for e in mine if e["name"].startswith("round.")
                  and e["args"]["round"] == r["args"]["round"]]
        assert [e["name"] for e in inside] == [
            "round.schedule", "round.assemble", "round.launch",
            "round.fetch", "round.emit", "round.account"]
        assert abs(sum(e["dur"] for e in inside) - r["dur"]) <= 6 * eps
        assert r["args"]["tokens"] == sum(r["args"]["row_lens"]) \
            <= r["args"]["pad"]
        assert all("cpu_us" in e for e in inside + [r])
    if family != "gpt":                  # the layers' reports, a round each
        assert sum(e["name"] == "moe.route" for e in spans.events) == rounds


def test_sampling_and_captured_logits_ride_the_same_fetch(models,
                                                          monkeypatch):
    """What a round reads only sometimes (the logit rows, for a request
    that samples or a test that captures them) is part of the round's one
    fetch, never a second blocking call."""
    eng = _engine(models, "gpt")
    log = _watch(eng, monkeypatch)
    eng.capture_logits = []
    greedy = GenerationRequest(IDS[:9].tolist(), max_new_tokens=5)
    sampled = GenerationRequest(IDS[9:20].tolist(), max_new_tokens=5,
                                temperature=0.8, top_k=20)
    eng.submit_request(greedy)
    eng.submit_request(sampled)
    eng.run_until_idle()
    st = eng.stats()
    assert st["round_fetches"] == log["gets"] == st["steps"]
    assert all(log["copies"]) and len(log["copies"]) == 2 * st["steps"]
    assert len(sampled.generated) == 5 and eng.capture_logits
    assert greedy.generated == _dense(models["gpt"], IDS[:9].tolist(), 5)


# ------------------------------------------ (b) the message, there and back

def _random_plan(rng, T, R, bt_shape, rows):
    parts = (rng.integers(0, 1 << 30, T), rng.integers(0, T + 1, R),
             rng.integers(0, 9, R), rng.integers(0, 1 << 20, R),
             rng.integers(0, 1 << 16, bt_shape))
    parts = [p.astype(np.int32) for p in parts]
    for p in parts[1:4]:
        p[rows:] = 0                   # rows the round does not use
    parts[4][..., rows:, :] = 0
    return parts


@pytest.mark.parametrize("T,R,bt_shape,rows", [
    (8, 3, (3, 5), 0), (32, 4, (4, 7), 4), (16, 4, (2, 4, 6), 3),
    (544, 32, (2, 32, 72), 32)],
    ids=["empty-rows", "full-round", "two-groups", "two-groups-full-size"])
def test_plan_message_packs_and_unpacks(T, R, bt_shape, rows):
    """The host writes the plan through ``_plan_parts``' views of one
    buffer; the program takes the uploaded message apart with the same
    function: the five arrays come back as they went in."""
    rng = np.random.default_rng(T + R)
    want = _random_plan(rng, T, R, bt_shape, rows)
    message = np.full(_message_len(T, R, bt_shape), -1, np.int32)
    assert message.size == T + 3 * R + int(np.prod(bt_shape))
    views = _plan_parts(message, R, bt_shape)
    for view, part in zip(views, want):
        assert view.base is not None and view.shape == part.shape
        view[...] = part
    assert (message != -1).sum() >= message.size - 8    # every word written
    got = jax.jit(lambda m: _plan_parts(m, R, bt_shape))(
        jax.device_put(message))
    for g, w in zip(got, want):
        assert g.dtype == jnp.int32 and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), w)


def test_plan_buffer_is_kept_a_pad_and_reset(models):
    """One host buffer a token pad, reused: a round allocates nothing and
    starts from a plan of no rows (unused rows at the sentinel T)."""
    eng = _engine(models, "afmoe-two-groups")
    message, parts = eng._plan(16)
    assert parts[4].shape == eng._bt_shape() == (2, 3, eng.max_pages)
    assert message.shape == (_message_len(16, 3, eng._bt_shape()),)
    for p in parts:
        p[...] = 7
    again, parts2 = eng._plan(16)
    assert again is message and all(a is b for a, b in zip(parts, parts2))
    assert not parts[0].any() and (parts[1] == 16).all()
    assert not any(p.any() for p in parts[2:])
    assert eng._plan(8)[0] is not message


# ------------------------------- (c) the logits come back bit for bit

def test_emitted_logits_are_bit_equal_through_the_int32_view(models):
    """``top`` leaves the program as int32 bits beside the tokens and the
    host views it back: the same float32 numbers the seven-argument body
    returns, bit for bit."""
    eng = _engine(models, "mla-emit-logits")
    R = eng.max_slots
    message, (tokens, row_starts, row_lens, kv_lens, bt) = eng._plan(8)
    tokens[:5] = IDS[:5]
    row_starts[:2], row_lens[:2], kv_lens[:2] = [0, 3], [3, 2], [3, 2]
    bt[0, 0], bt[1, 0] = 1, 2
    # the seven-argument body reads the pools and gives them back; the
    # same pools then go through the round's entry
    nxt, _, _, extras = eng._build_ragged_step()(
        eng._param_arrays, *(jnp.asarray(p) for p in (
            tokens, row_starts, row_lens, kv_lens, bt)), eng.kv.pools)
    out, _, _, _ = eng._ragged_fn(eng._param_arrays,
                                  jax.device_put(message), eng.kv.pools)
    out = np.asarray(out)
    assert out.dtype == np.int32 and out.shape == (2 * R,)
    np.testing.assert_array_equal(out[:R], np.asarray(nxt))
    want = np.asarray(extras["top"])
    assert want.dtype == np.float32
    np.testing.assert_array_equal(out[R:], want.view(np.int32))
    # an engine without emit_logits returns the tokens alone
    plain = _engine(models, "gpt")
    out, _, _, _ = plain._ragged_fn(
        plain._param_arrays, jax.device_put(plain._plan(8)[0]),
        plain.kv.pools)
    assert out.shape == (plain.max_slots,) and out.dtype == jnp.int32


def test_emitted_logit_is_the_fetched_rows_top(models):
    """End to end: the logit a greedy request records for a token is the
    largest of that round's logit row, the same float32."""
    eng = _engine(models, "mla-emit-logits")
    eng.capture_logits = []
    req = GenerationRequest(IDS[:13].tolist(), max_new_tokens=6)
    eng.submit_request(req)
    eng.run_until_idle()
    # the first token comes with the prompt's last chunk; every later one
    # from a decode round, whose rows capture_logits kept
    tops = [float(cap[slot].max()) for slots, cap in eng.capture_logits
            for slot, rid in slots.items() if rid == req.request_id]
    assert len(req.token_logits) == 6 and len(tops) == 5
    assert req.token_logits[1:] == tops


# -------------------- (e) the body keeps the seven arguments of the tools

@pytest.mark.parametrize("family", ["gpt", "afmoe-two-groups"])
def test_the_rounds_body_lowers_with_seven_arguments(models, family):
    """``benchmark/tools/compile_serve_v5e.py`` / ``compile_groups_v5e.py``
    and ``tests/test_tpu_compile.py`` lower ``_build_ragged_step()`` with
    params, the five plan arrays and the pools: that signature stays, and
    the round's own entry is ONE program around it."""
    eng = _engine(models, family)
    R = eng.max_slots

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    params = [_aval(a) for a in eng._param_arrays]
    pools = jax.tree_util.tree_map(_aval, eng.kv.pools)
    body = jax.jit(eng._build_ragged_step(), donate_argnums=(6,))
    lowered = body.lower(params, i32(16), i32(R), i32(R), i32(R),
                         i32(*eng._bt_shape()), pools)
    nxt, row_logits, pools_out, extras = lowered.out_info
    assert nxt.shape == (R,) and row_logits.shape[0] == R
    assert set(extras) == {"aux"}
    assert jax.tree_util.tree_structure(pools_out) \
        == jax.tree_util.tree_structure(pools)
    # the entry: the same program behind one message, in ONE module
    entry = eng._ragged_fn.lower(
        params, i32(_message_len(16, R, eng._bt_shape())), pools)
    out, row_logits2, _, aux = entry.out_info
    assert out.shape == (R,) and row_logits2.shape == row_logits.shape
    assert set(aux) == set(extras["aux"])
    eng.warm_ragged(16)
    assert eng.compiled_text(16).count("ENTRY") == 1


def test_a_traced_round_counts_the_ragged_kernels_work_items(models):
    """``attn_items`` / ``attn_page_blocks`` on ``decode_round``: the work
    items of the GPT kernel that read a page and the page blocks they
    fetch, a layer, from the round's rows and token pad as
    ``item_plan`` plans them, whatever backend runs (here the XLA
    reference). A prompt of 21 tokens goes as chunks of 8, 8 and 5 (one
    item each at a pad of 8), the second prompt's 5 tokens beside its
    first decode row; a decode row is one item; every item here reads its
    pages in one block (gpt_tiny's heads are 16 wide: 1,024 keys a
    block)."""
    from paddle_tpu.ops.pallas.ragged_attention import (item_plan,
                                                        item_schedule)
    eng = _engine(models, "gpt")
    spans = tracing.start()
    try:
        for a, b in ((0, 21), (30, 35)):
            eng.submit(IDS[a:b].tolist(), max_new_tokens=3)
        eng.run_until_idle()
        rounds = [e["args"] for e in spans.events
                  if e.get("ph") == "X" and e["name"] == "decode_round"
                  and "pad" in e["args"]]
    finally:
        tracing.stop()
    head_dim = models["gpt"].config.hidden_size \
        // models["gpt"].config.num_heads
    for r in rounds:
        block_q, pages = item_schedule(4, head_dim, r["pad"])
        assert (r["attn_items"], r["attn_page_blocks"]) == item_plan(
            r["row_lens"], r["kv_lens"], 4, block_q, pages)
    assert [r["row_lens"] for r in rounds] == [[8], [8], [5], [1, 5],
                                               [1, 1], [1]]
    assert [r["attn_items"] for r in rounds] == [1, 1, 1, 2, 2, 1]
    assert [r["attn_page_blocks"] for r in rounds] == [1, 1, 1, 2, 2, 1]
