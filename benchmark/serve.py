"""What serving cells share: one ``ServingEngine`` with its own serve-loop
thread, and this (main) thread as the load generator. Two threads in all.
The load itself is the kind's: ``open_loop.py``, ``closed_loop.py``.

Definitions (also in README.md):

* warm-up: the start-up gate, ``warm_ragged`` (every token pad the engine
  can launch) and ``warm_requests`` whole requests; all set-up.
* closed loop: every client sends its next request from the completion
  callback of its last. The loop starts in set-up; the window opens when
  every client's first request has produced its first token, lasts
  ``--seconds``, and counts the tokens whose time falls inside it.
* open loop: request i is *due* at ``t_open + due_i`` whatever the engine
  does. TTFT runs from the due time, not from ``submit``. Lateness is
  submit time minus due time. Arrivals stop at ``--seconds``; the drain
  then lasts at most ``drain_s``, after which an unfinished request has
  failed and its TTFT, if it has none, is the time it waited.
"""
import gc
import threading
import time

import numpy as np

from . import traffic
from .harness import host_use, median, pct, say, within

# keys of a serving mix's file that some code reads (``why``-like prose
# apart); each kind adds its own. harness.check_keys refuses any other.
KEYS = {"": {"prompt_len", "output_len", "warm_requests",
             "warm_output_tokens", "trace_seconds", "correct"},
        "correct": {"sample", "logit_gap_abs", "logit_gap_reason",
                    "reference_pad"}}


class Sent:
    """One request as the generator saw it."""
    __slots__ = ("req", "due", "sent", "client")

    def __init__(self, req, due, sent, client=None):
        self.req, self.due, self.sent, self.client = req, due, sent, client


class GcWatch:
    """Python's collector, as the window sees it. A full collection of
    this process's heap holds the interpreter lock for 75-110 ms (measured
    here on the CPU: 195,000 tracked objects after imports and warm-up),
    long enough to push a request into the next round when it lands
    between two rounds; it came in some runs' windows and not in others'.
    So the heap as it stands after warm-up is collected once and frozen
    (``gc.freeze``: later collections look only at what the window itself
    allocates), and every collection inside the window is timed."""

    def __init__(self):
        self.pauses = []                # (start, seconds, generation)
        self._t0 = None

    def __enter__(self):
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        gc.unfreeze()

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))

    def report(self, t_open, t_close):
        inside = [p for p in self.pauses if t_open <= p[0] <= t_close]
        full = [p for p in inside if p[2] == 2]
        say(f"garbage collections inside the window: {len(inside)}, "
            f"{len(full)} of them full, "
            f"{1e3 * sum(p[1] for p in inside):.1f} ms in all, the longest "
            f"{1e3 * max([p[1] for p in inside] or [0.0]):.1f} ms")


def submit(eng, r, on_done=None):
    from paddle_tpu.serving.scheduler import GenerationRequest
    prompt = r["prompt"]
    if not isinstance(prompt, list):
        prompt = prompt.tolist()    # a later block's ids, cut from an array
    req = GenerationRequest(prompt, max_new_tokens=r["max_new_tokens"],
                            on_done=on_done)
    return eng.submit_request(req, block=False)


def run(run, fam, tracer, t_process, loop, closed):
    """``loop(run, eng, tracer, t_process) -> (sent, t_open, t_close, host)``
    is the kind's load; ``closed`` says whether the window cuts requests
    (closed loop) or every request of it is drained (open loop)."""
    cfg, wl = run.cell.config, run.cell.workload
    lap = _Lap()
    model = fam.build_model(cfg, run.seed)
    say(f"model built in {lap()}")
    eng = fam.make_engine(model, cfg)
    pool = eng.kv.nbytes()
    say(f"engine up in {lap()}: attention backend "
        f"{eng.attn_backend!r}, start-up gate {eng.attn_ab}; pool "
        f"{pool / 2**30:.2f} GiB")
    pads = eng.warm_ragged()
    say(f"warm_ragged: token pads {pads} in {lap()}")
    spans = None
    if tracer.on:
        from paddle_tpu.observability import tracing
        spans = tracing.start()
    eng.start()
    try:
        # whole requests through submit, admission, chunked prefill and a
        # few decode rounds; their outputs are cut short, the path is not
        warm = [dict(r, max_new_tokens=int(wl["warm_output_tokens"]))
                for r in traffic.request_stream(
                    wl, cfg["vocab_size"], run.seed + 1,
                    int(wl["warm_requests"]))]
        for req in [submit(eng, r) for r in warm]:
            req.result(timeout=120.0)
        say(f"{len(warm)} warm request(s) in {lap()}")
        with GcWatch() as watch:
            t_warm = time.perf_counter()
            sent, t_open, t_close, host = loop(run, eng, tracer, t_process)
    finally:
        # the engine first: whatever is still in flight ends here and not
        # while the profiler writes its file
        run.counters = eng.stats()
        eng.close()
        tracer.stop()
    if spans is not None:
        from paddle_tpu.observability import tracing
        run.spans = [e for e in spans.events if e.get("ph") == "X"]
        tracing.stop()
    run.window_s = t_close - t_open
    # the engine's spans carry time.time(); the window on that clock
    skew = time.time() - time.perf_counter()
    run.window_wall = (t_open + skew, t_close + skew)
    run.requests = sent
    say(f"set-up: {run.setup_s:.2f} s, {sum(host[0]):.2f} s of CPU; the "
        f"kind's own before the window (a collection, a head, a ramp) "
        f"{t_open - t_warm:.2f} s")
    _metrics(run, sent, t_open, t_close, closed)
    _check(run, fam, model, sent, t_close)
    c = run.counters
    watch.report(t_open, t_close)
    user, kernel = (b - a for a, b in zip(*host))
    say(f"host in the window: {user + kernel:.2f} s of CPU, {kernel:.2f} s "
        "of it in the kernel")
    used = c["kv_occupancy_peak_pct"] / 100.0
    say(f"KV pool: at its fullest {used * int(cfg['engine']['num_pages']):.0f}"
        f" of {cfg['engine']['num_pages']} pages held a token "
        f"({used * pool / 2**30:.2f} GiB of the {pool / 2**30:.2f} GiB "
        "reserved); memory_peak_bytes in the result line counts the whole "
        "reservation, not this")
    say(f"engine counters: decode_tokens {c['decode_tokens']}, "
        f"prefill_chunk_tokens {c['prefill_chunk_tokens']}, rounds "
        f"{c['steps']}, kv_occupancy_peak_pct {c['kv_occupancy_peak_pct']}, "
        f"evictions {c['evictions']}, distinct_programs "
        f"{c['distinct_programs']}, pads {c['ragged_token_pads']}, prefix "
        f"hits {c.get('prefix_hits')}")
    if not within(run, "pads_not_warmed",
                  len(set(c["ragged_token_pads"]) ^ set(pads)), 0):
        say(f"NOT steady: a token pad outside the warmed {pads} compiled "
            f"inside the run: {c['ragged_token_pads']}")
        run.correct = False
    if tracer.on:
        from .trace_reduce import pallas_instructions
        t0 = time.perf_counter()
        run.pallas_ops = pallas_instructions(
            eng.compiled_text(max(pads)))
        say(f"round program at pad {max(pads)}: Pallas custom calls "
            f"{sorted(run.pallas_ops)[:6]} "
            f"({time.perf_counter() - t0:.1f} s)")
        run.trace = tracer.summary(run.pallas_ops)


class _Lap:
    """``lap()`` -> the wall and CPU seconds since the last call, as text:
    a set-up phase that reads slower on more CPU ran on a slower host."""

    def __init__(self):
        self.t, self.c = time.perf_counter(), sum(host_use())

    def __call__(self):
        t, c = time.perf_counter(), sum(host_use())
        out = f"{t - self.t:.1f} s ({c - self.c:.1f} s of CPU)"
        self.t, self.c = t, c
        return out


def stop_later(tracer, stretch_s):
    """Stop the device trace after its stretch from a helper thread, so
    the generator is not held up while the profiler writes its file."""
    if tracer.on:
        t = threading.Timer(stretch_s, tracer.stop)
        t.daemon = True
        t.start()


def _metrics(run, sent, t_open, t_close, closed):
    """Every end-to-end quantity this kind of cell can report; the harness
    prints the ones BENCHMARK.json declares for the cell. Names of the
    form ``ttft_p<q>_ms`` and ``itl_p<q>_ms`` are computed for whichever
    percentiles the cell declares."""
    now = time.perf_counter()
    gaps, ttft, in_window = [], [], 0
    failed = 0
    for s in sent:
        r = s.req
        times = list(r.token_times)
        if closed:
            # tokens and gaps that fall inside the window; a request cut
            # by the window's end has not failed
            in_window += sum(t_open < t <= t_close for t in times)
            gaps += [b - a for a, b in zip(times, times[1:])
                     if t_open < b <= t_close]
            failed += r.error is not None and r.t_done is not None \
                and r.t_done <= t_close
            continue
        unfinished = r.state != "finished"
        failed += unfinished
        gaps += [b - a for a, b in zip(times, times[1:])]
        in_window += sum(t_open < t <= t_close for t in times)
        if r.t_first_token is not None:
            ttft.append(r.t_first_token - s.due)
        else:
            ttft.append(now - s.due)     # the worst: it never answered
    run.attempted, run.failed = len(sent), int(failed)
    run.e2e["serve_tokens_per_s"] = in_window / (t_close - t_open)
    want = [m["name"] for m in run.cell.end_to_end]
    for name in want:
        for prefix, vals in (("ttft_p", ttft), ("itl_p", gaps)):
            if name.startswith(prefix) and name.endswith("_ms") and vals:
                q = float(name[len(prefix):-3])
                run.e2e[name] = 1e3 * pct(vals, q)
    if closed and gaps:
        _stops(sent, gaps, t_open, t_close)
    ended = sum(s.req.t_done is not None and s.req.t_done <= t_close
                for s in sent)
    say(f"{len(sent)} requests sent, {failed} failed, {ended} ended inside "
        f"the window ({ended / (t_close - t_open):.3f}/s); {in_window} "
        f"output tokens inside the {t_close - t_open:.2f} s window; "
        f"{len(gaps)} "
        f"inter-token gaps, median "
        f"{1e3 * median(gaps) if gaps else float('nan'):.2f} ms, p99 "
        f"{1e3 * pct(gaps, 99) if gaps else float('nan'):.2f} ms"
        + (f"; TTFT over {len(ttft)} requests median "
           f"{1e3 * median(ttft):.1f} ms, p90 {1e3 * pct(ttft, 90):.1f} ms; "
           f"median of the first half of arrivals "
           f"{1e3 * median(ttft[:len(ttft) // 2 or 1]):.1f} ms, of the "
           f"second {1e3 * median(ttft[len(ttft) // 2:]):.1f} ms; requests "
           "in flight (due, not finished) at each eighth of the window "
           f"{_in_flight(sent, t_open, t_close, now)} (a backlog that grows "
           "shows as a count that climbs to the close; one bunch of the "
           "frozen sequence moves a half's median as much)"
           if ttft else ""))


def _stops(sent, gaps, t_open, t_close):
    """Where a closed loop stood still. Its engine is never without work,
    so two successive tokens (of any request) lie a round apart at most;
    a longer gap is a stop of the engine, the host or the machine. A run
    that reads far from its twins on identical work says here whether it
    lost its tokens in one stop, in many, or in none (every round slower).
    Judges nothing."""
    limit = max(5.0 * median(gaps), 2.0 * pct(gaps, 99))
    marks = sorted({t for s in sent for t in s.req.token_times
                    if t_open < t <= t_close})
    stops = [(b - a, a) for a, b in zip([t_open] + marks, marks + [t_close])
             if b - a > limit]
    longest, at = max(stops, default=(0.0, t_open))
    say(f"stops: {len(stops)} gap(s) longer than {1e3 * limit:.0f} ms (five "
        "medians, twice the p99) between successive tokens of any request "
        f"inside the window, {sum(d for d, _ in stops):.3f} s in all"
        + (f", the longest {longest:.3f} s, {at - t_open:.2f} s after the "
           "window opened" if stops else ""))


def _in_flight(sent, t_open, t_close, now):
    marks = [t_open + (t_close - t_open) * k / 8 for k in range(1, 9)]
    return [sum(1 for s in sent if s.due <= t
                and (s.req.t_done if s.req.t_done is not None else now) > t)
            for t in marks]


def _check(run, fam, model, sent, t_close):
    """``correct``: for a seeded sample of finished requests, the plain
    reference's full forward over prompt plus generated tokens must put
    the engine's token within the stated logit tolerance of its own top
    logit, at the first generated position (prefill) and at the last
    (decode through the cache). Logits, not token identity: with random
    weights the top logit changes on rounding."""
    tol = run.cell.workload["correct"]
    done = [s for s in sent if s.req.state == "finished"
            and s.req.t_done <= t_close + float(
                run.cell.workload.get("drain_s", 0.0))]
    rng = traffic.rng_for(run.seed, 4)
    k = min(int(tol["sample"]), len(done))
    if k == 0:
        say("NOT correct: no request finished, nothing to hold to the "
            "reference")
        run.correct = False
        return
    picks = [done[i] for i in sorted(rng.choice(len(done), k,
                                                replace=False))]
    weights = fam.reference_weights(model)
    pad = int(tol["reference_pad"])
    worst = 0.0
    t0 = time.perf_counter()
    for s in picks:
        p, g = s.req.prompt_ids, s.req.generated
        seq = np.zeros(pad, np.int32)
        ctx = list(p) + list(g[:-1])
        if len(ctx) > pad:
            raise RuntimeError(f"reference_pad {pad} < context {len(ctx)}")
        seq[:len(ctx)] = ctx
        where = [len(p) - 1, len(ctx) - 1]
        rows = np.asarray(fam.reference.logits_at(weights, seq, where))
        for row, tok, what in zip(rows, (g[0], g[-1]), ("first", "last")):
            gap = float(row.max() - row[tok])
            worst = max(worst, gap)
            say(f"reference: request with prompt {len(p)}, {len(g)} tokens: "
                f"{what} token {tok} sits {gap:.4f} below the reference's "
                f"top logit")
    run.correct = within(run, "token_logit_gap", worst,
                         float(tol["logit_gap_abs"]))
    say(f"reference check of {k} requests in "
        f"{time.perf_counter() - t0:.1f} s: worst gap {worst:.4f}, "
        f"tolerance {tol['logit_gap_abs']} ({tol['logit_gap_reason']}): "
        f"{'correct' if run.correct else 'NOT correct'}")
