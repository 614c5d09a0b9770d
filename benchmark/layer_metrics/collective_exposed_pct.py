"""The part of ``collective_pct`` during which no other operation runs on
that device: what overlap could still hide."""
LAYER = "sharding"
MOVES = "train_tokens_per_s"


def read(run):
    return None if run.trace is None else run.trace.collective_exposed_pct
