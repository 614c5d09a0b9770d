"""The latent attention kernel's share of its roofline: the least time the
chip could take for the attention each round needs, over the time the
kernel's events took. For every round whose program lies wholly inside the
traced stretch: the latent layers (``layer_types_run.count("mla")`` where
the configuration names its layers' kinds, else every layer) x
``flops.roofline_seconds`` of
``flops_mla_moe.mla_ragged(row_lens, kv_lens, ...)`` from that round's own
``decode_round`` span (joined by ``round``; the span's ``latent_rows`` is
the sum of ``kv_lens`` and is checked against it), over the
``mla_ragged_attention*`` events inside those programs. A program without
the kernel or the span gives nothing to read."""
from benchmark import flops, flops_mla_moe, host_trace
from benchmark.harness import say
from benchmark.peaks import peaks_for

LAYER = "kernels"
MOVES = "itl_p99_ms"
KERNEL = "mla_ragged_attention"


def read(run):
    ht = host_trace.of_run(run)
    cfg = run.cell.config
    if ht is None or "kv_lora_rank" not in cfg:
        return None
    peaks = peaks_for(run.device_kind)
    value = cfg["kv_lora_rank"]
    latent = value + cfg["qk_rope_head_dim"]
    kinds = cfg.get("layer_types_run")
    layers = list(kinds).count("mla") if kinds else cfg["num_layers"]
    least = kernel = 0.0
    bound = {}
    for chip in ht.chips:
        for rnd, prog in host_trace.round_programs(ht, chip):
            ns = host_trace.kernel_ns(chip, KERNEL, prog) \
                if host_trace.inside(ht, prog) else 0
            if not ns or "latent_rows" not in rnd.stats:
                continue
            kv_lens = host_trace.ints(rnd.stats["kv_lens"])
            if sum(kv_lens) != int(rnd.stats["latent_rows"]):
                raise RuntimeError(
                    f"round {rnd.stats['round']}: latent_rows "
                    f"{rnd.stats['latent_rows']} is not the sum of kv_lens")
            t, which = flops.roofline_seconds(*flops_mla_moe.mla_ragged(
                host_trace.ints(rnd.stats["row_lens"]), kv_lens,
                cfg["num_attention_heads"], latent, value), peaks)
            least += layers * t
            kernel += ns / 1e9
            bound[which] = bound.get(which, 0) + 1
    if not kernel:
        return None
    say(f"latent attention kernel: {kernel:.4f} s in {sum(bound.values())} "
        f"rounds against a roofline of {least:.4f} s in {layers} latent "
        f"layer(s) (rounds bound by {bound})")
    if least > kernel:
        raise RuntimeError(
            f"mla_roofline_pct.serve would read {100 * least / kernel:.1f}: "
            "the count of latent layers or of their rows is too high")
    return 100.0 * least / kernel
