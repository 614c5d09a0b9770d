"""The whole serving round's share of the chip's bf16 peak over the traced
stretch's whole rounds (``benchmark/round_mfu.py``): model FLOPs of the
real tokens, not of the pad, whatever kernel computes them. Beside
``attn_roofline_pct.serve`` where the cell is judged by its tokens a
second."""
from benchmark import round_mfu

LAYER = "serving round"
MOVES = "serve_tokens_per_s"


def read(run):
    return round_mfu.gpt(run)
