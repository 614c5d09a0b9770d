"""Model FLOP/s utilization: FLOPs the forward and backward passes need
per token (``benchmark/flops.py``; attention counted causal, recomputation
not counted) times the tokens per second of the median step, over the
cell's chips times the published bf16 peak (``benchmark/peaks.py``)."""
import statistics

from benchmark.peaks import peaks_for

LAYER = "step program"
MOVES = "train_tokens_per_s"


def read(run):
    if not run.step_s or not run.flops_per_token:
        return None
    tokens_per_s = run.tokens_per_step / statistics.median(run.step_s)
    peak = peaks_for(run.device_kind)["bf16_flops_per_s"] * run.chips
    return 100.0 * run.flops_per_token * tokens_per_s / peak
