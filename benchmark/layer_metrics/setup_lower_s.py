"""Seconds of set-up in which the process traced a jaxpr or lowered one to
an MLIR module: the union of the ``jit.trace`` and ``jit.lower`` events of
``tracing.compile_log()`` that ended before the window opened. A program
is traced and lowered again at every start whatever the compile cache
holds, so this is the part of ``setup_s`` that a kernel's size, a jit
inside a jit or a token pad more costs every run (PERF.md section 6,
PR 34 and PR 36). The log begins where the engine or the first
``to_static`` build starts it, so what compiled before that (the weights'
initializers) is not in it. Also prints the same for ``jit.compile`` (a
load from the compile cache included), the ten costliest ``fun_name``\\ s
and the events that ended INSIDE the window (there should be none).

``Run`` holds ``setup_s`` and not the instant it is counted from: under
the contract's command ``benchmark/run.py`` is ``__main__`` and its
``T_PROCESS`` is on ``time.perf_counter()``, the log's clock, so the
window opened at ``T_PROCESS + run.setup_s``; a test that drives
``harness.run_cell`` itself has that instant as ``run_cell``'s
``t_process``, a frame up the stack. Where neither is there, or the
program has no compile log (the parent of PR 37), there is nothing to
read."""
import sys
import time

from benchmark import host_phases
from benchmark.harness import say

LAYER = "programs (trace, lower, compile)"
MOVES = "setup_s"


def process_start():
    t = getattr(sys.modules.get("__main__"), "T_PROCESS", None)
    frame = sys._getframe(1)
    while t is None and frame is not None:
        if frame.f_code.co_name == "run_cell":
            t = frame.f_locals.get("t_process")
        frame = frame.f_back
    return t


def window(run, t_process):
    """The window on ``time.perf_counter()``: a serving run says when it
    closed on ``time.time()``; a training run's closes ``window_s`` after
    it opened (a traced run's profiler start and stop come on top: the
    last seconds of such a window are not looked at)."""
    t_open = t_process + run.setup_s
    if run.window_wall[1] != float("inf"):
        return t_open, run.window_wall[1] - (time.time()
                                             - time.perf_counter())
    return t_open, t_open + run.window_s


def read(run):
    from paddle_tpu.observability import tracing
    t_process = process_start()
    if t_process is None or not hasattr(tracing, "compile_log"):
        return None
    t_open, t_close = window(run, t_process)
    got = host_phases.compile_split(tracing.compile_log(), t_open, t_close)
    if not got["events"]:
        return None
    say(f"compile log before the window ({got['events']} events): tracing "
        f"or lowering under way {got['lower_s']:.2f} s (plain sum "
        f"{got['lower_sum_s']:.2f} s: an inner jit's trace lies inside its "
        f"caller's), compiling or loading from the cache "
        f"{got['compile_s']:.2f} s, of a set-up of {run.setup_s:.1f} s")
    say("the ten costliest fun_names (trace + lower + compile seconds, "
        "events): " + "; ".join(f"{fun} {s:.2f} s x{n}"
                                for fun, s, n in got["costliest"]))
    inside = sorted(got["inside"], key=lambda e: -e[3])
    say("compile events that ended inside the window (expected none): "
        + (f"{len(inside)}, the longest: " + "; ".join(
            f"{kind} {fun} {s:.3f} s at +{t - t_open:.2f} s"
            for t, kind, fun, s in inside[:12]) if inside else "none"))
    return got["lower_s"]
