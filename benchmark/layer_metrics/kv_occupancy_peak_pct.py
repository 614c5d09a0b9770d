"""Highest share of the page pool in use at the end of any round, from
``ServingEngine.stats()`` (warm traffic included)."""
LAYER = "scheduler and cache"
MOVES = "itl_p99_ms"


def read(run):
    return run.counters.get("kv_occupancy_peak_pct")
