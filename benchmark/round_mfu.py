"""The whole serving round's share of the chip's peak: the model FLOPs the
real tokens of the traced stretch's whole rounds needed, over the seconds
from the first such round's program to the last's end times the published
bf16 peak. It holds whatever kernel or compiler code implements the
attention, so a claim stays bounded where a later PR renames a kernel or
takes it off the path and its ``*_roofline_pct`` falls silent.

Counted per round from its own ``decode_round`` annotation (joined to its
program by ``host_trace.round_programs``), never from the pad: 2 FLOPs a
token and matrix weight of the blocks, one head product a ROW (a row emits
at most one token, so a chunk's other tokens need no logits; embedding
lookups are not products), and every layer's attention over the keys each
token sees (``flops.ragged``). The rounds are back to back and the count
leaves out everything a program does beyond the mathematics, so the share
cannot pass 100 %.
"""
from . import flops, host_trace
from .harness import say
from .peaks import peaks_for


def gpt(run):
    """-> the share in %, or None without a device trace, whole rounds or
    a dense GPT configuration."""
    ht = host_trace.of_run(run)
    cfg = run.cell.config
    if ht is None or cfg.get("family") != "gpt":
        return None
    h, layers = cfg["hidden_size"], cfg["num_layers"]
    block = flops.gpt_matmul_params(h, cfg["intermediate_size"], layers, 0)
    head = cfg["vocab_size"] * h
    need = tokens = rounds = 0
    seconds = 0.0
    for chip in ht.chips:
        whole = [(r, p) for r, p in host_trace.round_programs(ht, chip)
                 if host_trace.inside(ht, p)]
        if not whole:
            continue
        for rnd, _ in whole:
            row_lens = host_trace.ints(rnd.stats["row_lens"])
            attn, _ = flops.ragged(
                row_lens, host_trace.ints(rnd.stats["kv_lens"]),
                cfg["num_heads"], cfg["num_heads"], cfg["head_dim"])
            need += 2 * block * sum(row_lens) + layers * attn \
                + 2 * head * sum(n > 0 for n in row_lens)
            tokens += sum(row_lens)
        rounds += len(whole)
        seconds += (whole[-1][1][1] - whole[0][1][0]) / 1e9
    if not seconds:
        return None
    peak = peaks_for(run.device_kind)["bf16_flops_per_s"]
    say(f"round mfu: {need / 1e12:.4f} TFLOP for {tokens} real tokens in "
        f"{rounds} whole rounds over {seconds:.4f} s of the chip(s) "
        f"({need / seconds / 1e12:.3f} TFLOP/s a chip of {peak / 1e12:.0f})")
    return 100.0 * need / (seconds * peak)
