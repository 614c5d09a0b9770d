"""Median time of one whole training step in the window, host clock
around ``block_until_ready`` on the loss."""
import statistics

LAYER = "step program"
MOVES = "train_tokens_per_s"


def read(run):
    if not run.step_s:
        return None
    return 1e3 * statistics.median(run.step_s)
