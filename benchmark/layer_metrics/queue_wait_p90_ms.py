"""90th percentile of ``GenerationRequest.queue_wait_s`` over the window's
requests: submit to admission, re-admissions after eviction included."""
from benchmark.harness import pct

LAYER = "scheduler and cache"
MOVES = "ttft_p90_ms"


def read(run):
    waits = [s.req.queue_wait_s for s in run.requests
             if s.req.t_admit is not None]
    return 1e3 * pct(waits, 90) if waits else None
