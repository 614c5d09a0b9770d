"""Median device idle between the end of one round's program and the
start of the next round's, over the gaps in which the serve loop never
waited for work (``benchmark/host_trace.py``: rounds joined to programs
through the ``round.launch`` annotation; no ``serve.idle_wait`` overlaps
the gap). What the host costs the device a round when work is pending."""
import statistics

from benchmark import host_trace

LAYER = "serving round"
MOVES = "itl_p99_ms"


def read(run):
    ht = host_trace.of_run(run)
    gaps = host_trace.round_gaps_ms(ht) if ht is not None else []
    return statistics.median(gaps) if gaps else None
