"""The ragged paged attention kernel's share of its roofline: the least
time the chip could take for the attention each round needs, over the time
the kernel's events took. For every round whose program lies wholly
inside the traced stretch: ``num_layers`` x ``flops.roofline_seconds`` of
``flops.ragged(row_lens, kv_lens, ...)`` from that round's own span (joined
by ``round``), over the ``ragged_paged_attention*`` events inside those
programs. Counted from what the rounds carried, never from how many
kernel calls were made."""
from benchmark import flops, host_trace
from benchmark.harness import say
from benchmark.peaks import peaks_for

LAYER = "kernels"
MOVES = "itl_p99_ms"


def read(run):
    ht = host_trace.of_run(run)
    if ht is None:
        return None
    cfg = run.cell.config
    peaks = peaks_for(run.device_kind)
    least = kernel = 0.0
    bound = {}
    for chip in ht.chips:
        for rnd, prog in host_trace.round_programs(ht, chip):
            ns = host_trace.kernel_ns(chip, host_trace.RAGGED, prog) \
                if host_trace.inside(ht, prog) else 0
            if not ns:
                continue
            t, which = flops.roofline_seconds(*flops.ragged(
                host_trace.ints(rnd.stats["row_lens"]),
                host_trace.ints(rnd.stats["kv_lens"]),
                cfg["num_heads"], cfg["num_heads"], cfg["head_dim"]),
                peaks)
            least += cfg["num_layers"] * t
            kernel += ns / 1e9
            bound[which] = bound.get(which, 0) + 1
    if not kernel:
        return None
    say(f"ragged kernel: {kernel:.4f} s in {sum(bound.values())} rounds "
        f"against a roofline of {least:.4f} s (rounds bound by {bound})")
    return 100.0 * least / kernel
