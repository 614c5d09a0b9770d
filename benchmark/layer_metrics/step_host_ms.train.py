"""Median host time of one training step's call: ``step.gather`` +
``step.launch`` + ``step.rebind`` over the steps of the traced stretch
(the ``train_step`` annotations on ``/host:CPU`` of the run's
``.xplane.pb``, ``benchmark/host_phases.py``). The call returns once the
program is enqueued; the wait for the loss after it is the caller's. Also
prints each phase's median. A program that records no ``train_step`` gives
nothing to read."""
import statistics

from benchmark import host_phases
from benchmark.harness import say

LAYER = "step program"
MOVES = "train_tokens_per_s"


def read(run):
    st = host_phases.steps_of_run(run)
    values, by_phase = host_phases.step_host_ms(st) if st is not None \
        else ([], {})
    if not values:
        return None
    say(f"train_step over {len(values)} steps of the stretch, median ms: "
        + ", ".join(f"{name} {ms:.3f}" for name, ms in by_phase.items()))
    return statistics.median(values)
