"""Median duration of the engine's own ``decode_round`` spans (host clock,
one span per scheduler round) that end inside the window."""
import statistics

LAYER = "serving round"
MOVES = "itl_p99_ms"


def read(run):
    lo, hi = (1e6 * t for t in run.window_wall)
    durs = [e["dur"] / 1e3 for e in run.spans
            if e.get("name") == "decode_round" and lo <= e["ts"]
            and e["ts"] + e["dur"] <= hi]
    return statistics.median(durs) if durs else None
