"""Per-request distributed tracing units (ISSUE 20).

Covers the tracing buffer's tail-based sampling machinery, the
truncation marker (satellite: the buffer used to stop recording
silently at the cap), the pid-namespaced request-id fallback
(satellite: per-process counters aliased across engines), the
structural zero-overhead contract for tracing OFF, tracing-ON greedy
parity, and the ``trace_report`` CLI. The multi-process fleet soak
(cross-process waterfalls) lives in test_serving_fleet.py.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.observability import tracing


@pytest.fixture(scope="module")
def tiny_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    paddle.seed(7)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


# ------------------------------------------------- satellite: truncation

def test_truncation_marker_and_drop_counter(monkeypatch, tmp_path):
    """At _MAX_EVENTS the buffer drops — but visibly: one over-cap
    metadata marker, a dropped counter, and the registry metric."""
    from paddle_tpu.observability import metrics as obsm
    monkeypatch.setattr(tracing, "_MAX_EVENTS", 4)
    reg = obsm.enable(out_dir=str(tmp_path), interval_s=0)
    buf = tracing.start(path=str(tmp_path / "t.json"), rank=0)
    for i in range(9):
        buf.add(f"ev{i}", i * 1.0, 0.5)
    assert buf.dropped == 5
    doc = buf.to_dict()
    marks = [e for e in doc["traceEvents"]
             if e.get("name") == "trace_truncated"]
    assert len(marks) == 1           # first drop only, not per drop
    assert marks[0]["ph"] == "M"
    assert marks[0]["args"]["at_events"] == 4
    assert doc["droppedEvents"] == 5
    snap = reg.snapshot()
    assert snap["counters"]["trace_events_dropped_total"] == 5
    tracing.stop()


def test_request_events_respect_cap(monkeypatch, tmp_path):
    """Kept request traces flushing into a full buffer count their lost
    events instead of silently vanishing."""
    monkeypatch.setattr(tracing, "_MAX_EVENTS", 2)
    buf = tracing.start(path=str(tmp_path / "t.json"), rank=0)
    ctx = tracing.mint_context()
    for i in range(6):
        tracing.req_event(ctx, f"s{i}", i * 1.0, 0.1)
    assert tracing.finish_request(ctx, error=True) is True
    # lane-name M event + 1 span fit (cap 2); marker is over-cap
    assert buf.dropped >= 4


# ---------------------------------------------------- tail-based sampling

def test_tail_sampling_keep_and_drop(tmp_path):
    buf = tracing.start(path=str(tmp_path / "t.json"), rank=0)
    assert buf.sample_rate is None  # env unset by conftest scrub

    def n_request_events():
        return sum(1 for e in buf.events
                   if (e.get("args") or {}).get("trace"))

    # uninteresting + no sampling -> dropped before export
    ctx = tracing.mint_context()
    tracing.req_event(ctx, "queue_wait", 1.0, 0.5)
    assert tracing.finish_request(ctx, dur_s=0.01) is False
    assert n_request_events() == 0
    assert buf.req_traces_dropped == 1
    # each interesting flag retains on its own
    for kw in ({"error": True}, {"hedged": True}, {"evicted": True},
               {"aborted": True}, {"migrated": True}):
        c = tracing.mint_context()
        tracing.req_event(c, "queue_wait", 1.0, 0.5)
        assert tracing.finish_request(c, **kw) is True, kw
    assert n_request_events() == 5
    tracing.stop()


def test_tail_sampling_slow_threshold(monkeypatch, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_TRACE_SLOW_MS", "100")
    buf = tracing.start(path=str(tmp_path / "t.json"), rank=0)
    assert buf.slow_ms == 100.0
    fast, slow = tracing.mint_context(), tracing.mint_context()
    tracing.req_event(fast, "decode", 1.0, 0.01)
    tracing.req_event(slow, "decode", 1.0, 0.5)
    assert tracing.finish_request(fast, dur_s=0.05) is False
    assert tracing.finish_request(slow, dur_s=0.5) is True
    tracing.stop()


def test_sampled_is_deterministic_per_trace_id():
    """Every process hashes the same trace id to the same verdict —
    the cross-process agreement needs no wire bits."""
    assert tracing.sampled("anything", 1.0) is True
    assert tracing.sampled("anything", 0.0) is False
    assert tracing.sampled("anything", None) is False
    ids = [os.urandom(8).hex() for _ in range(400)]
    verdicts = {t: tracing.sampled(t, 0.5) for t in ids}
    assert {tracing.sampled(t, 0.5) for t in ids for _ in range(2)} \
        <= {True, False}
    for t, v in verdicts.items():
        assert tracing.sampled(t, 0.5) is v   # stable on re-ask
    kept = sum(verdicts.values())
    assert 80 < kept < 320   # roughly half, loose bound


def test_verdict_gates_late_events(tmp_path):
    """Post-verdict events follow the decision: dropped traces stay
    dropped, kept traces keep accepting (hedge_lost after fleet_done),
    and a later keep upgrades only future events."""
    buf = tracing.start(path=str(tmp_path / "t.json"), rank=0)

    def names():
        return [e["name"] for e in buf.events
                if (e.get("args") or {}).get("trace")]

    kept = tracing.mint_context()
    tracing.req_event(kept, "route", 1.0, 0.1)
    assert tracing.finish_request(kept, hedged=True) is True
    tracing.req_event(kept, "hedge_lost", 2.0, 0.0)   # late, lands
    assert names() == ["route", "hedge_lost"]

    dropped = tracing.mint_context()
    tracing.req_event(dropped, "route", 1.0, 0.1)
    assert tracing.finish_request(dropped) is False
    tracing.req_event(dropped, "leg_abort", 2.0, 0.0)  # late, vanishes
    assert names() == ["route", "hedge_lost"]
    # a second, interesting terminal (e.g. the router after an engine
    # leg already dropped) upgrades the verdict for future events
    assert tracing.finish_request(dropped, error=True) is True
    tracing.req_event(dropped, "ledger_replay", 3.0, 0.0)
    assert names() == ["route", "hedge_lost", "ledger_replay"]
    tracing.stop()


def test_undecided_traces_flush_at_export(tmp_path):
    path = str(tmp_path / "t.json")
    tracing.start(path=path, rank=0)
    ctx = tracing.mint_context()
    tracing.req_event(ctx, "queue_wait", 1.0, 0.5)   # never finished
    tracing.stop()
    doc = json.load(open(path))
    assert any(e.get("name") == "queue_wait"
               for e in doc["traceEvents"])


def test_mint_context_none_when_off():
    assert tracing.mint_context() is None
    # and the feeds are no-ops for a None ctx
    tracing.req_event(None, "x", 0.0, 0.0)
    assert tracing.finish_request(None) is False


# -------------------------------------- satellite: rid fallback namespace

def test_fallback_rid_is_pid_namespaced():
    """Two engine PROCESSES minting fallback rids must never alias:
    the high bits carry the pid component, the low bits the counter."""
    from paddle_tpu.serving.scheduler import GenerationRequest, _RID_NS
    a = GenerationRequest([1, 2])
    b = GenerationRequest([1, 2])
    assert _RID_NS == (os.getpid() & 0xFFFFF) << 20
    assert a.request_id >> 20 == os.getpid() & 0xFFFFF
    assert a.request_id != b.request_id
    assert isinstance(a.request_id, int)   # rng() seed arithmetic
    # explicit ids pass through untouched
    assert GenerationRequest([1], request_id="r1").request_id == "r1"


# -------------------------------------------- structural zero-overhead

def test_tracing_off_structurally_zero_overhead(tiny_model, monkeypatch):
    """Tracing OFF: the scheduler round and the serve loop make ZERO
    calls into the tracing feeds and allocate ZERO trace state — the
    counting-dict convention. One module gate check per round is the
    entire budget."""
    calls = {"req_event": 0, "finish_request": 0, "add": 0,
             "req_add": 0, "phase": 0, "thread_time_ns": 0}

    def count(key, ret=None):
        def h(*a, **k):
            calls[key] += 1
            return ret
        return h

    monkeypatch.setattr(tracing, "req_event", count("req_event"))
    monkeypatch.setattr(tracing, "finish_request",
                        count("finish_request", False))
    monkeypatch.setattr(tracing.TraceBuffer, "add", count("add"))
    monkeypatch.setattr(tracing.TraceBuffer, "req_add",
                        count("req_add"))
    # the round/phase primitive: constructing one is already a call
    monkeypatch.setattr(tracing, "phase", count("phase"))
    # ... and the second clock of a phase is read behind the gate only
    monkeypatch.setattr(time, "thread_time_ns", count("thread_time_ns", 0))
    from tests.test_serving import _engine
    eng = _engine(tiny_model)
    # direct-step path
    r1 = eng.submit([1, 2, 3, 4], max_new_tokens=3)
    while not r1.done():
        eng.step()
    assert r1.trace is None          # no context ever minted
    # serve-loop path
    eng.start()
    r2 = eng.submit([5, 6, 7], max_new_tokens=3)
    assert len(r2.result(30)) == 3
    time.sleep(0.06)                 # the idle loop ticks a few times
    eng.close()
    assert calls == {"req_event": 0, "finish_request": 0, "add": 0,
                     "req_add": 0, "phase": 0, "thread_time_ns": 0}


def test_phase_records_twice_from_one_call(monkeypatch):
    """``phase`` writes the buffer event and opens/closes a profiler
    annotation of the same name; lists ride as space-separated text
    (the profiler splits an annotation's arguments on commas), late
    arguments reach both, ``then`` hands the round on to the next phase,
    and a phase with no buffer goes to the annotation alone."""
    log = []

    class Ann:
        is_enabled = staticmethod(lambda: True)   # a profile is running

        def __init__(self, name, **kw):
            self.name = name
            log.append(("init", name, kw))

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

        def set_metadata(self, **kw):
            log.append(("meta", self.name, kw))

    monkeypatch.setattr(tracing, "_ANNOTATION", Ann)
    buf = tracing.TraceBuffer(rank=0)
    rnd = tracing.phase(buf, "decode_round", round=7).open()
    ph = tracing.phase(buf, "round.schedule", round=7).open()
    rnd.set(pad=8, row_lens=[1, 3])
    ph = ph.then("round.assemble")
    ph.close()
    rnd.close()
    with tracing.phase(buf, "serve.idle_wait"):
        pass
    tracing.phase(None, "decode_round", round=8).open().close()
    assert log == [
        ("init", "decode_round", {"round": 7}), ("enter", "decode_round"),
        ("init", "round.schedule", {"round": 7}),
        ("enter", "round.schedule"),
        ("meta", "decode_round", {"pad": 8, "row_lens": "1 3"}),
        ("exit", "round.schedule"),
        ("init", "round.assemble", {"round": 7}),
        ("enter", "round.assemble"), ("exit", "round.assemble"),
        ("exit", "decode_round"),
        ("init", "serve.idle_wait", {}), ("enter", "serve.idle_wait"),
        ("exit", "serve.idle_wait"),
        ("init", "decode_round", {"round": 8}), ("enter", "decode_round"),
        ("exit", "decode_round")]
    assert [(e["name"], e.get("args")) for e in buf.events] == [
        ("round.schedule", {"round": 7}), ("round.assemble", {"round": 7}),
        ("decode_round", {"round": 7, "pad": 8, "row_lens": [1, 3]}),
        ("serve.idle_wait", None)]
    assert all(e["cat"] == "serving" and e["ph"] == "X"
               for e in buf.events)


ROUND_PHASES = ["round.schedule", "round.assemble", "round.launch",
                "round.fetch", "round.emit", "round.account"]


def test_tracing_on_round_phases(tiny_model):
    """Tracing ON: every round yields ONE ``decode_round`` that says what
    it launched (``pad``, ``tokens``, ``row_lens``, ``kv_lens``,
    ``round``) with its six phases nested inside it in order, and an
    idle serve loop yields ``serve.idle_wait`` between ``serve.turn``s;
    the traced twin stays token-identical."""
    from tests.test_serving import _engine
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    eng = _engine(tiny_model)
    base = eng.generate(prompt, max_new_tokens=5)
    eng.close()
    buf = tracing.start()
    try:
        eng = _engine(tiny_model)
        req = eng.submit(prompt, max_new_tokens=5)
        while not req.done():
            eng.step()
        rounds_run = eng._steps
        eng.start()                  # nothing pending: the loop idles
        time.sleep(0.1)
        eng.close()
    finally:
        tracing.stop()
    assert req.result(1) == base
    events = [e for e in buf.events if e.get("cat") == "serving"]
    rounds = [e for e in events if e["name"] == "decode_round"]
    assert [e["args"]["round"] for e in rounds] == list(range(rounds_run))
    # round 0: the whole 10-token prompt as one prefill row, padded to 16
    first = rounds[0]["args"]
    assert (first["pad"], first["tokens"]) == (16, 10)
    assert first["row_lens"] == [10] and first["kv_lens"] == [10]
    assert first["prefill_rows"] == 1 and first["decode_rows"] == 0
    # then one decode row a round: one token against a growing context
    for i, e in enumerate(rounds[1:], 1):
        a = e["args"]
        assert (a["pad"], a["tokens"], a["row_lens"]) == (8, 1, [1])
        assert a["kv_lens"] == [10 + i] and a["decode_rows"] == 1
    eps = 1.0                        # µs: float rounding of ts + dur
    for e in rounds:
        inside = [c for c in events if c["name"].startswith("round.")
                  and c["args"]["round"] == e["args"]["round"]]
        assert [c["name"] for c in inside] == ROUND_PHASES
        # the launch says how many transfers went up, the fetch how many
        # blocking fetches came back (one each); the other phases carry
        # the round alone
        own = {"round.launch": {"uploads": 1}, "round.fetch": {"fetches": 1}}
        for c in inside:
            assert c["args"] == dict(own.get(c["name"], {}),
                                     round=e["args"]["round"])
        assert e["ts"] - eps <= inside[0]["ts"]
        for a, b in zip(inside, inside[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + eps
        assert inside[-1]["ts"] + inside[-1]["dur"] <= \
            e["ts"] + e["dur"] + eps
        assert sum(c["dur"] for c in inside) <= e["dur"] + eps
    idle = [e for e in events if e["name"] == "serve.idle_wait"]
    assert idle and all(e["dur"] <= 0.5e6 for e in idle)
    assert {e["name"] for e in events} == \
        {"decode_round", "serve.idle_wait", "serve.turn", *ROUND_PHASES}


SERVE_SPANS = ("decode_round", "serve.idle_wait", "serve.turn")


def _served(tiny_model, on_token=None, prompts=((3, 1, 4, 1, 5, 9, 2, 6),
                                                 (2, 7, 1, 8))):
    """An engine driven through ``_serve_loop`` with tracing on: idle
    ticks, two bursts of requests with an idle stretch between them ->
    (buffer, serve thread's id, the engine's stats)."""
    from tests.test_serving import _engine
    buf = tracing.start()
    try:
        eng = _engine(tiny_model)
        eng.start()
        time.sleep(0.05)                 # the loop idles before any work
        for burst in (prompts, prompts[:1]):
            reqs = [eng.submit(list(p), max_new_tokens=5,
                               on_token=on_token) for p in burst]
            for r in reqs:
                assert len(r.result(60)) == 5
            time.sleep(0.05)
        tid = eng._thread.ident
        stats = eng.stats()
        eng.close()
    finally:
        tracing.stop()
    return buf, tid, stats


@pytest.fixture(scope="module")
def served(tiny_model):
    return _served(tiny_model)


def test_serve_thread_spans_tile_its_time(served):
    """Tracing ON, through ``_serve_loop``: from the first round's opening
    to the last one's close every instant of the serve thread lies in a
    ``decode_round``, a ``serve.idle_wait`` or the ``serve.turn`` between
    them: no hole over 1 ms, holes under 2 % in sum, no overlap."""
    buf, tid, _ = served
    spans = sorted((e for e in buf.events if e["name"] in SERVE_SPANS),
                   key=lambda e: e["ts"])
    assert {e["tid"] for e in spans} == {tid}
    rounds = [i for i, e in enumerate(spans) if e["name"] == "decode_round"]
    assert len(rounds) >= 8
    spans = spans[rounds[0]:rounds[-1] + 1]
    assert {e["name"] for e in spans} == set(SERVE_SPANS)
    whole = spans[-1]["ts"] + spans[-1]["dur"] - spans[0]["ts"]
    holes = [b["ts"] - (a["ts"] + a["dur"]) for a, b in zip(spans, spans[1:])]
    eps = 1.0                            # us: float rounding of ts + dur
    assert min(holes) >= -eps            # disjoint on the thread
    assert max(holes) <= 1e3
    assert sum(h for h in holes if h > 0) <= 0.02 * whole
    # a turn is what lies between two of the others, never beside a turn
    for a, b in zip(spans, spans[1:]):
        assert (a["name"] == "serve.turn") != (b["name"] == "serve.turn")


def test_six_phases_tile_their_round(served):
    """The six ``round.*`` phases follow one another from their round's
    opening to its close with no hole, and ``round.account`` is the
    last."""
    buf, _, stats = served
    events = [e for e in buf.events if e.get("cat") == "serving"]
    rounds = [e for e in events if e["name"] == "decode_round"]
    assert len(rounds) == stats["steps"]
    eps = 1.0
    for r in rounds:
        inside = [c for c in events if c["name"].startswith("round.")
                  and c["args"]["round"] == r["args"]["round"]]
        assert [c["name"] for c in inside] == ROUND_PHASES
        assert abs(inside[0]["ts"] - r["ts"]) <= eps
        for a, b in zip(inside, inside[1:]):
            assert abs(b["ts"] - (a["ts"] + a["dur"])) <= eps
        assert abs(inside[-1]["ts"] + inside[-1]["dur"]
                   - (r["ts"] + r["dur"])) <= eps
        assert abs(sum(c["dur"] for c in inside) - r["dur"]) <= 6 * eps


@pytest.mark.parametrize("name", SERVE_SPANS + tuple(ROUND_PHASES))
def test_every_phase_records_its_cpu_time(served, name):
    """Every ``round.*`` / ``serve.*`` record (and the round's own) has
    ``cpu_us``, the thread's CPU time between the same two instants: never
    negative and at most the duration plus the clocks' resolution; a wait
    is off the CPU."""
    buf, _, _ = served
    mine = [e for e in buf.events if e["name"] == name]
    assert mine and all("cpu_us" in e for e in mine)
    slack = 1e6 * time.get_clock_info("thread_time").resolution + 50.0
    for e in mine:
        assert 0.0 <= e["cpu_us"] <= e["dur"] + slack
    if name == "serve.idle_wait":
        waits = [e for e in mine if e["dur"] >= 15e3]
        assert waits and all(e["cpu_us"] < 0.5 * e["dur"] for e in waits)
    if name == "decode_round":
        for r in mine:
            inside = [c for c in buf.events if c["name"].startswith("round.")
                      and c["args"]["round"] == r["args"]["round"]]
            assert abs(sum(c["cpu_us"] for c in inside) - r["cpu_us"]) <= 1.0


def test_a_round_with_nothing_to_launch_is_recorded(tiny_model):
    """A round that finds nothing to launch leaves its time under a named
    span in the buffer too: a ``decode_round`` that says no ``pad``, with
    the phases it went through, and the step counter moves on."""
    from tests.test_serving import _engine
    buf = tracing.start()
    try:
        eng = _engine(tiny_model)
        assert eng.step() == 0
        assert eng.generate([1, 2, 3], max_new_tokens=2)
        eng.close()
    finally:
        tracing.stop()
    events = [e for e in buf.events if e.get("cat") == "serving"]
    rounds = [e for e in events if e["name"] == "decode_round"]
    assert [e["args"]["round"] for e in rounds] == list(range(len(rounds)))
    assert rounds[0]["args"] == {"round": 0}
    assert [e["name"] for e in events if e["name"].startswith("round.")
            and e["args"]["round"] == 0] == \
        ["round.schedule", "round.assemble", "round.account"]
    assert all("pad" in e["args"] for e in rounds[1:])
    # step() drives rounds from any thread: no serve loop, no serve.turn
    assert not [e for e in events if e["name"].startswith("serve.")]


def test_a_stall_names_its_cause(tiny_model):
    """While the buffer is on a collection is one ``host.gc`` event on the
    thread it ran in (inside the phase it held up), and a first launch at
    a new token pad shows as ``jit.trace`` / ``jit.lower`` / ``jit.compile``
    of the round's program inside that round's ``round.launch``."""
    import gc
    import threading
    fired = []

    def on_token(req, tok, finished):
        if not fired:
            fired.append(threading.get_ident())
            gc.collect()                 # on the serve thread, in round.emit

    buf, tid, _ = _served(tiny_model, on_token=on_token)
    assert fired == [tid]
    buf2 = tracing.start()
    gc.collect(1)                        # ... and one on this thread
    tracing.stop()
    assert tracing._on_gc not in gc.callbacks      # out with the buffer
    here = [e for e in buf2.events if e["name"] == "host.gc"]
    assert [(e["tid"], e["args"]["generation"]) for e in here] == \
        [(threading.get_ident(), 1)]
    full = [e for e in buf.events if e["name"] == "host.gc"
            and e["args"]["generation"] == 2]
    assert len(full) == 1 and full[0]["tid"] == tid
    assert set(full[0]["args"]) == {"generation", "collected"}

    def inside(ev, name):
        return [p for p in buf.events if p["name"] == name
                and p["tid"] == ev["tid"] and p["ts"] <= ev["ts"] + 1.0
                and ev["ts"] + ev["dur"] <= p["ts"] + p["dur"] + 1.0]

    assert inside(full[0], "round.emit")
    # the engine never warmed its pads: each pad's first round compiles
    # (jax names the trace ``round_step``, the rest ``jit(round_step)``)
    jit = [e for e in buf.events if e["name"].startswith("jit.")
           and "round_step" in e["args"]["fun_name"]]
    assert {e["name"] for e in jit} == {"jit.trace", "jit.lower",
                                        "jit.compile"}
    pads = {r["args"]["pad"] for r in buf.events
            if r["name"] == "decode_round"}
    for kind in ("jit.trace", "jit.lower", "jit.compile"):
        mine = [e for e in jit if e["name"] == kind]
        assert len(mine) == len(pads)
        for e in mine:
            assert e["tid"] == tid and e["cat"] == "jit"
            assert inside(e, "round.launch")


def test_compile_log_grows_at_a_compile_and_not_at_a_cached_call(
        tiny_model):
    """``stats()["compile"]`` totals the always-on compile log (tracing
    OFF here): a first launch at a token pad adds a trace, a lowering and
    a compile of ``round_step``; a launch at a pad already served adds
    nothing."""
    from tests.test_serving import _engine
    assert not tracing.enabled()
    eng = _engine(tiny_model)
    before = eng.stats()["compile"]
    assert set(before) == {"trace_s", "lower_s", "compile_s", "events"}
    t0 = time.perf_counter()
    eng.generate([1, 2, 3, 4, 5], max_new_tokens=3)     # pads 8 and... 8
    first = eng.stats()["compile"]
    log = [e for e in tracing.compile_log()
           if e[0] >= t0 and "round_step" in e[2]]
    assert sorted(e[1] for e in log) == ["jit.compile", "jit.lower",
                                         "jit.trace"]
    assert all(e[3] > 0.0 and e[0] <= time.perf_counter() for e in log)
    assert first["events"] >= before["events"] + 3
    for key in ("trace_s", "lower_s", "compile_s"):
        assert first[key] > before[key]
    eng.generate([5, 4, 3, 2, 1], max_new_tokens=3)     # the same pad
    assert eng.stats()["compile"] == first
    eng.close()


def test_buffer_at_its_cap_keeps_the_newest(monkeypatch, tmp_path):
    """At the cap the OLDEST events go (an eighth of the cap at a time)
    and the newest stay, so a process left tracing holds the minutes
    before a stall; the export says how many went, and a reader that
    iterates ``events`` sees ordinary events only."""
    monkeypatch.setattr(tracing, "_MAX_EVENTS", 64)
    buf = tracing.start(path=str(tmp_path / "t.json"), rank=0)
    for i in range(200):
        buf.add(f"ev{i}", float(i), 0.5)
    names = [e["name"] for e in buf.events if e.get("ph") == "X"]
    assert len(names) == len(buf.events)          # no marker among them
    assert 64 - 8 <= len(names) <= 64
    assert names == [f"ev{i}" for i in range(200 - len(names), 200)]
    assert buf.dropped == 200 - len(names)
    doc = json.loads(open(tracing.stop()).read())
    marks = [e for e in doc["traceEvents"]
             if e.get("name") == "trace_truncated"]
    assert len(marks) == 1 and marks[0]["ph"] == "M"
    assert marks[0]["args"]["at_events"] == 64
    assert marks[0]["args"]["dropped"] == doc["droppedEvents"] == buf.dropped
    assert doc["traceEvents"][-1]["name"] == "ev199"


def test_tracing_on_greedy_parity(tiny_model, tmp_path):
    """The traced twin generates token-identical output — tracing
    observes the round, never perturbs it."""
    from tests.test_serving import _engine
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    eng = _engine(tiny_model)
    base = eng.generate(prompt, max_new_tokens=6)
    eng.close()
    path = str(tmp_path / "trace.0.json")
    tracing.start(path=path, rank=0)
    eng2 = _engine(tiny_model)
    traced = eng2.generate(prompt, max_new_tokens=6)
    req = eng2.submit(prompt, max_new_tokens=6)
    while not req.done():
        eng2.step()
    assert req.trace is not None
    eng2.close()
    tracing.stop()
    assert traced == base
    assert req.result(1) == base
    doc = json.load(open(path))
    names = {e["name"] for e in doc["traceEvents"]
             if (e.get("args") or {}).get("trace")}
    # the full local lifecycle is spanned (sampling: slow/err flags off,
    # but undecided-at-export traces flush — generate()'s finished trace
    # was dropped, the un-finished twin would flush; the engine decides
    # at terminal, so assert via an explicitly sampled run instead)
    assert {"enqueue", "queue_wait"} <= names or names == set()


def test_sampled_run_exports_full_lifecycle(tiny_model, monkeypatch,
                                            tmp_path):
    """PADDLE_TPU_TRACE_SAMPLE=1.0 retains every trace: the exported
    lifecycle covers submit -> admit -> prefill -> decode -> done, plus
    the engine-lane decode_round spans."""
    monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "1.0")
    from tests.test_serving import _engine
    path = str(tmp_path / "trace.0.json")
    tracing.start(path=path, rank=0)
    eng = _engine(tiny_model)
    eng.generate([2, 7, 1, 8], max_new_tokens=4)
    eng.close()
    tracing.stop()
    doc = json.load(open(path))
    req_names = {e["name"] for e in doc["traceEvents"]
                 if (e.get("args") or {}).get("trace")}
    assert {"enqueue", "queue_wait", "prefill_chunk", "first_token",
            "prefill", "decode", "request_done"} <= req_names
    eng_names = {e["name"] for e in doc["traceEvents"]
                 if e.get("cat") == "serving"}
    assert "decode_round" in eng_names
    rounds = [e for e in doc["traceEvents"]
              if e["name"] == "decode_round"]
    assert all("decode_rows" in (e.get("args") or {}) for e in rounds)


# -------------------------------------------------- phase histogram feed

def test_serving_phase_ms_family(tiny_model, monkeypatch, tmp_path):
    from paddle_tpu.observability import metrics as obsm
    from paddle_tpu.observability.report import build_run_report
    from tests.test_serving import _engine
    reg = obsm.enable(out_dir=str(tmp_path), interval_s=0)
    eng = _engine(tiny_model, registry=reg, engine_id="e7")
    eng.generate([1, 2, 3, 4, 5], max_new_tokens=3)
    eng.close()
    snap = reg.snapshot()
    keys = {k for k in snap["histograms"]
            if k.startswith("serving_phase_ms")}
    assert "serving_phase_ms{engine=e7,phase=queue_wait}" in keys
    assert "serving_phase_ms{engine=e7,phase=prefill}" in keys
    assert "serving_phase_ms{engine=e7,phase=decode}" in keys
    reg.flush()
    rep = build_run_report(
        __import__("paddle_tpu.observability.report",
                   fromlist=["read_rank_snapshots"])
        .read_rank_snapshots(str(tmp_path)))
    phases = rep["serving_phases"]["e7"]
    assert {"queue_wait", "prefill", "decode"} <= set(phases)
    assert phases["decode"]["count"] == 1


# ------------------------------------------------------ trace_report CLI

def _synthetic_trace(path, tid="feedbeef", pid=0, t0=1000.0):
    us = 1e6
    evs = [
        {"name": "client_submit", "ph": "X", "pid": pid, "tid": 1,
         "ts": t0 * us, "dur": 0.001 * us, "cat": "request",
         "args": {"trace": tid, "rid": "r1"}},
        {"name": "queue_wait", "ph": "X", "pid": pid, "tid": 1,
         "ts": (t0 + 0.01) * us, "dur": 0.02 * us, "cat": "request",
         "args": {"trace": tid}},
        {"name": "prefill", "ph": "X", "pid": pid + 1, "tid": 1,
         "ts": (t0 + 0.03) * us, "dur": 0.05 * us, "cat": "request",
         "args": {"trace": tid}},
        {"name": "decode", "ph": "X", "pid": pid + 1, "tid": 1,
         "ts": (t0 + 0.08) * us, "dur": 0.1 * us, "cat": "request",
         "args": {"trace": tid}},
        {"name": "hedge_fired", "ph": "X", "pid": pid, "tid": 1,
         "ts": (t0 + 0.09) * us, "dur": 0.0, "cat": "request",
         "args": {"trace": tid, "engine": "e1"}},
        {"name": "stream_token", "ph": "X", "pid": pid, "tid": 1,
         "ts": (t0 + 0.1) * us, "dur": 0.0, "cat": "request",
         "args": {"trace": tid, "i": 0}},
        {"name": "fleet_done", "ph": "X", "pid": pid, "tid": 1,
         "ts": (t0 + 0.18) * us, "dur": 0.0, "cat": "request",
         "args": {"trace": tid, "state": "finished", "hedged": True}},
    ]
    with open(path, "w") as f:
        json.dump({"traceEvents": evs}, f)


def test_trace_report_rows_and_flags(tmp_path):
    from paddle_tpu.observability import trace_report as tr
    _synthetic_trace(tmp_path / "trace.0.json")
    rows = tr.build_request_rows(tr.load_events(str(tmp_path)))
    assert set(rows) == {"feedbeef"}
    r = rows["feedbeef"]
    assert r["procs"] == 2                 # cross-process waterfall
    assert r["tokens"] == 1
    assert "hedged" in r["flags"]
    assert r["phases"]["queue_wait"] == pytest.approx(20.0, abs=1e-6)
    assert r["phases"]["prefill"] == pytest.approx(50.0, abs=1e-6)
    assert r["phases"]["decode"] == pytest.approx(100.0, abs=1e-6)
    assert r["e2e_ms"] == pytest.approx(180.0, abs=1e-3)
    rep = tr.rows_to_report(rows, top=3)
    assert rep[0]["trace"] == "feedbeef"
    assert rep[0]["decode_ms"] == pytest.approx(100.0, abs=1e-3)
    text = tr.format_request_rows(rows)
    assert "feedbeef" in text and "hedged" in text


def test_trace_report_cli(tmp_path):
    _synthetic_trace(tmp_path / "trace.0.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.trace_report",
         str(tmp_path), "--top", "5"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "feedbeef" in out.stdout
    assert "slowest" in out.stdout
    js = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.trace_report",
         str(tmp_path / "trace.0.json"), "--json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert js.returncode == 0, js.stderr
    assert json.loads(js.stdout)[0]["trace"] == "feedbeef"
    # empty dir: exit 1, not a crash
    empty = tmp_path / "empty"
    empty.mkdir()
    no = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.trace_report",
         str(empty)],
        capture_output=True, text=True, env=env, timeout=120)
    assert no.returncode == 1


def test_trace_report_dedups_merged_copy(tmp_path):
    """A log dir typically holds BOTH the per-process trace files and
    the merge_profiles output built from them; the same event must not
    count twice even though the merge rewrote its pid."""
    from paddle_tpu.observability import trace_report as tr
    _synthetic_trace(tmp_path / "trace.0.json")
    src = json.load(open(tmp_path / "trace.0.json"))["traceEvents"]
    merged = [{**e, "pid": 7} for e in src]   # merge rewrites pids
    with open(tmp_path / "merged.json", "w") as f:
        json.dump({"traceEvents": merged}, f)
    rows = tr.build_request_rows(tr.load_events(str(tmp_path)))
    r = rows["feedbeef"]
    assert r["phases"]["prefill"] == pytest.approx(50.0, abs=1e-6)
    assert r["phases"]["decode"] == pytest.approx(100.0, abs=1e-6)
    assert r["tokens"] == 1
    assert r["events"] == 7


def test_report_cli_slo_attribution_section(tmp_path):
    """report.py folds the trace files in the log dir into the
    slo_attribution section next to the metrics-derived sections."""
    from paddle_tpu.observability import report as obsrep
    _synthetic_trace(tmp_path / "trace.0.json")
    rep = {"ranks": {0: {"snapshots": 1, "steps": 0}}}
    # the section is built in main(); drive the builder directly
    from paddle_tpu.observability import trace_report as tr
    rows = tr.build_request_rows(tr.load_events(str(tmp_path)))
    rep["slo_attribution"] = tr.rows_to_report(rows, top=5)
    text = obsrep.format_run_report(rep)
    assert "slowest traced requests" in text
    assert "feedbeef" in text
