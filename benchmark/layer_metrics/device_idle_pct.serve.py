"""Device idle share of the traced stretch: 1 minus the union of the
device-operation intervals over the stretch, mean over devices."""
LAYER = "device"
MOVES = "itl_p99_ms"


def read(run):
    return None if run.trace is None else run.trace.idle_pct
