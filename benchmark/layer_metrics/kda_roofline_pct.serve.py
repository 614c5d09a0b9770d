"""The delta-rule recurrence kernel's share of its roofline: the least time
the chip could take for the recurrence each round needs, over the time the
kernel's events took. For every round whose program lies wholly inside the
traced stretch: the number of ``kda`` layers in ``layer_types_run`` x
``flops.roofline_seconds`` of ``flops_kda.kda_ragged(row_lens, ...)`` from
that round's own ``decode_round`` span (joined by ``round``; the span's
``state_rows`` is the launched rows x those layers and is checked against
them), over the ``kda_ragged*`` events inside those programs. A program
without the kernel or the span gives nothing to read."""
from benchmark import flops, flops_kda, host_trace
from benchmark.harness import say
from benchmark.peaks import peaks_for

LAYER = "kernels"
MOVES = "itl_p99_ms"
KERNEL = "kda_ragged"


def read(run):
    ht = host_trace.of_run(run)
    cfg = run.cell.config
    layers = list(cfg.get("layer_types_run", ())).count("kda")
    if ht is None or not layers:
        return None
    peaks = peaks_for(run.device_kind)
    least = kernel = 0.0
    bound = {}
    for chip in ht.chips:
        for rnd, prog in host_trace.round_programs(ht, chip):
            ns = host_trace.kernel_ns(chip, KERNEL, prog) \
                if host_trace.inside(ht, prog) else 0
            if not ns or "state_rows" not in rnd.stats:
                continue
            row_lens = host_trace.ints(rnd.stats["row_lens"])
            rows = sum(n > 0 for n in row_lens)
            if rows * layers != int(rnd.stats["state_rows"]):
                raise RuntimeError(
                    f"round {rnd.stats['round']}: state_rows "
                    f"{rnd.stats['state_rows']} is not {rows} rows x "
                    f"{layers} state layers")
            t, which = flops.roofline_seconds(*flops_kda.kda_ragged(
                row_lens, cfg["num_attention_heads"], cfg["head_dim"],
                cfg["head_dim"]), peaks)
            least += layers * t
            kernel += ns / 1e9
            bound[which] = bound.get(which, 0) + 1
    if not kernel:
        return None
    say(f"delta-rule kernel: {kernel:.4f} s in {sum(bound.values())} "
        f"rounds against a roofline of {least:.4f} s (rounds bound by "
        f"{bound})")
    return 100.0 * least / kernel
