"""Runner for serving cells of kind ``open_loop``: request i is *due* at
``t_open + due_i`` whatever the engine does (see serve.py's definitions).
"""
import time

from . import serve, traffic
from .harness import say

KEYS = {"": serve.KEYS[""] | {"rate_per_s", "rate_from", "drain_s"},
        "correct": serve.KEYS["correct"]}


def run(run, fam, tracer, t_process):
    serve.run(run, fam, tracer, t_process, _loop, closed=False)


def _loop(run, eng, tracer, t_process):
    wl, cfg = run.cell.workload, run.cell.config
    schedule = traffic.open_loop_schedule(wl, cfg["vocab_size"], run.seed,
                                          run.seconds)
    sent = []
    t_open = time.perf_counter()
    run.setup_s = t_open - t_process
    tracer.start()
    if tracer.on:
        t_open = time.perf_counter()
    serve.stop_later(tracer, float(wl["trace_seconds"]))
    host = [serve.host_use()]
    for due, r in schedule:
        wait = t_open + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        req = serve.submit(eng, r)
        sent.append(serve.Sent(req, t_open + due, time.perf_counter()))
    time.sleep(max(0.0, t_open + run.seconds - time.perf_counter()))
    t_close = time.perf_counter()
    host.append(serve.host_use())
    drain_end = t_close + float(wl["drain_s"])
    for s in sent:
        s.req._done.wait(max(0.0, drain_end - time.perf_counter()))
    late = [s.sent - s.due for s in sent]
    say(f"open loop: {len(sent)} arrivals at {wl['rate_per_s']}/s over "
        f"{run.seconds} s; generator lateness mean "
        f"{1e3 * sum(late) / len(late):.2f} ms, max {1e3 * max(late):.2f} "
        f"ms; drain took {time.perf_counter() - t_close:.2f} s of at most "
        f"{wl['drain_s']}")
    return sent, t_open, t_close, host
