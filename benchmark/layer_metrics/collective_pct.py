"""Share of the traced stretch a device spends in collective operations
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all),
mean over devices."""
LAYER = "sharding"
MOVES = "train_tokens_per_s"


def read(run):
    return None if run.trace is None else run.trace.collective_pct
