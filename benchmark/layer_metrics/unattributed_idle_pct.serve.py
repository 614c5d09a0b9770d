"""Share of the traced stretch in which the device is idle and NO span of
the serve thread covers the instant: ``host_trace.idle_by_phase``'s
``unattributed`` over the stretch. Since PR 37 the serve thread's time is
tiled by ``decode_round``, ``serve.idle_wait`` and ``serve.turn``, so what
is left lies truly outside the thread: before ``start()``, after
``stop()``, or the two clocks' disagreement at a span's edge. Also prints
the device's idle seconds under the two spans that PR added,
``serve.turn`` and ``round.account``. A program without ``serve.turn``
(the parent of that PR) gives nothing to read."""
from benchmark import host_phases, host_trace
from benchmark.harness import say

LAYER = "device"
MOVES = "itl_p99_ms"


def read(run):
    ht = host_trace.of_run(run)
    if ht is None or not any(s.name == host_phases.TURN for s in ht.serve):
        return None
    by_phase = host_trace.idle_by_phase(ht)
    if not by_phase:
        return None
    stretch = ht.window_ns / 1e9
    rounds = sum(s.name == host_trace.ROUND for s in ht.serve)
    say("device idle under the serve thread's new spans: "
        + ", ".join(f"{name} {by_phase.get(name, 0.0):.4f} s "
                    f"({1e3 * by_phase.get(name, 0.0) / rounds:.3f} ms a "
                    "round)" for name in (host_phases.TURN,
                                          host_phases.ACCOUNT))
        + f"; under no span {by_phase.get(host_trace.UNATTRIBUTED, 0.0):.4f}"
        f" s of {sum(by_phase.values()):.4f} s idle, over {rounds} rounds "
        f"and a stretch of {stretch:.3f} s")
    return 100.0 * by_phase.get(host_trace.UNATTRIBUTED, 0.0) / stretch
