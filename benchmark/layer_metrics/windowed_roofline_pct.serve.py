"""The windowed attention kernel's share of its roofline: the least time
the chip could take for the attention each round needs, over the time the
kernel's events took. For every round whose program lies wholly inside the
traced stretch: the sum over the configuration's layers, each with its own
window (``layer_types_run``: ``sliding_window`` tokens or all), of
``flops.roofline_seconds`` of ``flops_afmoe.windowed_ragged(row_lens,
kv_lens, ...)`` from that round's own ``decode_round`` span (joined by
``round``), over the ``windowed_ragged_attention*`` events inside those
programs. The span's ``kv_rows`` (what a full layer reads) and
``window_rows`` (what a window layer reads) are checked against the rows
the count takes as read. A program without the kernel or the span gives
nothing to read."""
from benchmark import flops, flops_afmoe, host_trace
from benchmark.harness import say
from benchmark.peaks import peaks_for

LAYER = "kernels"
MOVES = "itl_p99_ms"
KERNEL = "windowed_ragged_attention"


def read(run):
    ht = host_trace.of_run(run)
    cfg = run.cell.config
    if ht is None or "layer_types_run" not in cfg:
        return None
    peaks = peaks_for(run.device_kind)
    windows = [cfg["sliding_window"] if kind == "sliding_attention" else None
               for kind in cfg["layer_types_run"]]
    heads, kv_heads, dim = (cfg["num_attention_heads"],
                            cfg["num_key_value_heads"], cfg["head_dim"])
    row_bytes = 2 * kv_heads * dim * 2       # a token's keys and values
    least = kernel = 0.0
    n = 0
    for chip in ht.chips:
        for rnd, prog in host_trace.round_programs(ht, chip):
            ns = host_trace.kernel_ns(chip, KERNEL, prog) \
                if host_trace.inside(ht, prog) else 0
            if not ns or "kv_rows" not in rnd.stats:
                continue
            row_lens = host_trace.ints(rnd.stats["row_lens"])
            kv_lens = host_trace.ints(rnd.stats["kv_lens"])
            said = {None: int(rnd.stats["kv_rows"]),
                    cfg["sliding_window"]: int(rnd.stats.get("window_rows",
                                                             0))}
            for w in set(windows):
                ops, nbytes = flops_afmoe.windowed_ragged(
                    row_lens, kv_lens, heads, kv_heads, dim, w)
                rows = (nbytes - 2 * sum(row_lens) * heads * dim * 2) \
                    // row_bytes
                if rows != said[w]:
                    raise RuntimeError(
                        f"round {rnd.stats['round']}: the span says a layer "
                        f"of window {w} reads {said[w]} rows, the count "
                        f"takes {rows}")
                least += windows.count(w) * flops.roofline_seconds(
                    ops, nbytes, peaks)[0]
            kernel += ns / 1e9
            n += 1
    if not kernel:
        return None
    say(f"windowed attention kernel: {kernel:.4f} s in {n} rounds against "
        f"a roofline of {least:.4f} s over layers of windows {windows}")
    return 100.0 * least / kernel
