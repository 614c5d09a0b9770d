"""The whole serving round's share of the chip's bf16 peak, as
``round_mfu_pct.serve`` reads it (``benchmark/round_mfu.py``), under the
end-to-end metric every GPT serving cell reports: beside
``attn_roofline_pct.serve``, which moves ``itl_p99_ms`` too."""
from benchmark import round_mfu

LAYER = "serving round"
MOVES = "itl_p99_ms"


def read(run):
    return round_mfu.gpt(run)
