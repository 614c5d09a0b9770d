"""Share of the launched tokens that were padding: 100 x (1 - valid
tokens / padded tokens) over the engine's ``decode_round`` spans that end
inside the window (their ``tokens`` and ``pad``; host clock, as
``round_ms``). The round pays per padded token, so this is the part of
the kernel's time spent on rows that carry nothing."""
LAYER = "serving round"
MOVES = "itl_p99_ms"


def read(run):
    lo, hi = (1e6 * t for t in run.window_wall)
    rounds = [e.get("args") or {} for e in run.spans
              if e.get("name") == "decode_round" and lo <= e["ts"]
              and e["ts"] + e["dur"] <= hi]
    pad = sum(a.get("pad", 0) for a in rounds)
    if not pad:          # a program whose rounds do not say what they launch
        return None
    return 100.0 * (1.0 - sum(a.get("tokens", 0) for a in rounds) / pad)
