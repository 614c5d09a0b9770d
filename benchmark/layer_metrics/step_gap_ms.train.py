"""Median device idle between the end of one step's program and the start
of the next step's, mean over the chips: what the host and the caller cost
the device a step. Steps (``train_step`` annotations) and programs (``XLA
Modules``) are joined by order inside the stretch, and the counts are
printed (``benchmark/host_phases.py``). Also prints the device's idle
seconds by ``step.*`` phase and by "between steps" (the caller: the loss
fetch, the next batch), and the clocks' lag. A program that records no
``train_step`` gives nothing to read."""
import statistics

from benchmark import host_phases
from benchmark.harness import say

LAYER = "step program"
MOVES = "train_tokens_per_s"


def read(run):
    st = host_phases.steps_of_run(run)
    gaps = host_phases.step_gaps_ms(st) if st is not None else {}
    if not gaps:
        return None
    say("steps / programs / joined by chip: " + ", ".join(
        f"{name} {n} / {k} / {j}" for name, (n, k, j) in st.joined.items()))
    if st.lag_bounds is None:
        say("clocks: the runtime recorded no enqueue or completion events, "
            "so the device's and the host's are taken as one")
    else:
        low, up = (x / 1e6 for x in st.lag_bounds)
        say(f"clocks: the device's runs {low:.3f} to {up:.3f} ms behind the "
            f"host's, {st.lag_ns / 1e6:.3f} applied")
    by_phase = host_phases.step_idle_by_phase(st)
    steps = max(1, len(st.steps))
    say("device idle seconds by host phase: " + ", ".join(
        f"{name} {s:.4f} ({1e3 * s / steps:.3f} ms a step)" for name, s in
        sorted(by_phase.items(), key=lambda kv: -kv[1]))
        + f"; {sum(by_phase.values()):.4f} in all over a stretch of "
        f"{st.window_ns / 1e9:.3f} s and {len(st.steps)} steps")
    return statistics.mean(statistics.median(g) for g in gaps.values())
