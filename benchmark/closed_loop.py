"""Runner for serving cells of kind ``closed_loop``: every client sends
its next request from the completion callback of its last (see serve.py's
definitions).

A client never runs out of requests whatever the engine's speed: its
queue is ``requests_per_client`` requests a *block*, block after block
(``traffic.closed_loop_pool``). Block 0 is the frozen sequence every run
to date has sent; the blocks are made before the window opens, as many as
an engine whose mean round is ``FLOOR_ROUND_S`` could use up between the
loop's start and the window's end.
"""
import math
import threading
import time

from . import serve, traffic
from .harness import say

# the fastest mean round the pool is made for; under it the guard speaks
FLOOR_ROUND_S = 0.002

KEYS = {"": serve.KEYS[""] | {"clients", "requests_per_client",
                              "stagger_first", "ramp_timeout_s"},
        "correct": serve.KEYS["correct"]}


def run(run, fam, tracer, t_process):
    serve.run(run, fam, tracer, t_process, _loop, closed=True)


def _loop(run, eng, tracer, t_process):
    wl, cfg = run.cell.workload, run.cell.config
    per = int(wl["requests_per_client"])
    # a client takes one token a round; the first request may be cut
    rounds = math.ceil((run.seconds + float(wl["ramp_timeout_s"]))
                       / FLOOR_ROUND_S)
    t0 = time.perf_counter()
    blocks = traffic.closed_loop_pool(
        wl, cfg["vocab_size"], run.seed, per,
        rounds + traffic.longest(wl["output_len"]))
    n = len(blocks[0])
    say(f"closed loop: {len(blocks)} blocks of {n} x {per} requests made "
        f"in {time.perf_counter() - t0:.2f} s, enough for {rounds} rounds "
        f"a client (a mean round of {1e3 * FLOOR_ROUND_S:g} ms)")
    if wl.get("stagger_first"):
        # a closed loop in its steady state holds requests at every stage
        # of their output; cut each client's first request to a different
        # share of its length so the window opens on that mix and not on
        # n requests in step
        for i, q in enumerate(blocks[0]):
            q[0] = dict(q[0], max_new_tokens=max(
                2, int(q[0]["max_new_tokens"] * (i + 0.5) / n)))
    sent, lock = [], threading.Lock()
    cursor = [0] * n
    state = {"open": True, "errors": []}

    def send(c):
        i = cursor[c]
        if i >= per * len(blocks):
            state["errors"].append(
                f"client {c} ran out of requests after {len(blocks)} "
                f"blocks of {per}")
            return
        cursor[c] = i + 1
        now = time.perf_counter()
        req = serve.submit(eng, blocks[i // per][c][i % per],
                           on_done=lambda r, c=c: done(c))
        with lock:
            sent.append(serve.Sent(req, now, now, c))

    def done(c):
        # engine thread, from the round that emitted the last token: the
        # client's next request joins the very next round
        if state["open"]:
            try:
                send(c)
            except Exception as e:     # the engine swallows callback errors
                state["errors"].append(f"client {c}: {e!r}")

    for c in range(n):
        send(c)
    deadline = time.perf_counter() + float(wl["ramp_timeout_s"])
    while True:
        with lock:
            first = sent[:n]
        if all(s.req.t_first_token is not None for s in first):
            break
        if time.perf_counter() > deadline:
            raise RuntimeError("closed loop: not every client produced a "
                               f"token within {wl['ramp_timeout_s']} s")
        time.sleep(0.02)
    t_open = time.perf_counter()
    run.setup_s = t_open - t_process
    tracer.start()
    if tracer.on:
        t_open = time.perf_counter()
    serve.stop_later(tracer, float(wl["trace_seconds"]))
    host = [serve.host_use()]
    time.sleep(max(0.0, t_open + run.seconds - time.perf_counter()))
    t_close = time.perf_counter()
    host.append(serve.host_use())
    state["open"] = False
    with lock:
        sent = list(sent)
    if state["errors"]:
        raise RuntimeError(f"closed loop: {state['errors'][:3]}")
    say(f"closed loop: the furthest client sent {max(cursor)} requests, so "
        f"the run entered {-(-max(cursor) // per)} of its {len(blocks)} "
        "blocks")
    return sent, t_open, t_close, host
