"""CPU time of the serve thread a round: the mean over the window's rounds
of the ``cpu_us`` of a ``decode_round`` plus that of the ``serve.turn``
after it (the buffer's records, ``run.spans``; ``time.thread_time_ns()``
at the instants that give ``ts`` and ``dur``). What the host COMPUTES a
round, apart from what it waits for: a process in the slow mode (PERF.md
section 2 (c)) shows here and in the by-phase lines which phase grew, and
whether it grew on the CPU or off it. A mean and not a median because the
chip's host moves a thread's CPU clock in ticks of 10 ms (PR 37's first
chip run read a median of 0 in every phase): one round reads 0 or a tick,
the window's hundreds of rounds what a round costs. Also prints the median
wall and mean CPU time of every phase, the three longest single phases
with their round, and the ``host.gc`` / ``jit.*`` events inside the
window. A program whose records carry no ``cpu_us`` gives nothing to
read."""
import statistics

from benchmark import host_phases
from benchmark.harness import say

LAYER = "serving round"
MOVES = "itl_p99_ms"


def read(run):
    got = host_phases.round_host_cpu(run.spans, run.window_wall)
    if got is None:
        return None
    say(f"serve thread over {len(got['cpu_ms'])} rounds of the window:")
    for line in host_phases.cpu_lines(got):
        say(line)
    serve_tid = next(e["tid"] for e in run.spans
                     if e.get("name") == host_phases.ROUND)
    for line in host_phases.stall_lines(got["stalls"], serve_tid):
        say(line)
    return statistics.mean(got["cpu_ms"])
