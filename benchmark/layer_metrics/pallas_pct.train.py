"""Share of the device's busy time spent in Pallas custom calls: the
trace's ops whose text says ``custom_call_target="tpu_custom_call"``."""
LAYER = "kernels"
MOVES = "train_tokens_per_s"


def read(run):
    return None if run.trace is None else run.trace.pallas_pct_of_busy
