"""The flash attention kernels' share of their roofline: the least time
the chip could take for the attention the MODEL needs in the whole steps
the traced stretch holds, over the time the ``flash_attention*`` events
took in those steps; mean over the devices. The need is one device's
share of the configuration (sequences over ``sharding_degree``, heads over
``mp_degree``), forward and backward once per layer and step
(``flops.flash_fwd``, ``flops.flash_bwd``): recomputation is not counted,
nor how many kernel calls the implementation makes."""
from benchmark import flops, host_trace
from benchmark.harness import say
from benchmark.peaks import peaks_for

LAYER = "kernels"
MOVES = "train_tokens_per_s"


def read(run):
    ht = host_trace.of_run(run)
    if ht is None:
        return None
    cfg, par = run.cell.config, run.cell.config.get("parallel") or {}
    shape = (run.cell.workload["batch"]["sequences"]
             // int(par.get("sharding_degree", 1)),
             cfg["num_heads"] // int(par.get("mp_degree", 1)),
             cfg["max_seq_len"], cfg["head_dim"])
    peaks = peaks_for(run.device_kind)
    a_step = cfg["num_layers"] * sum(
        flops.roofline_seconds(*f(*shape), peaks)[0]
        for f in (flops.flash_fwd, flops.flash_bwd))
    shares = []
    for chip in ht.chips:
        steps = host_trace.whole_programs(ht, chip, host_trace.FLASH)
        if steps:
            kernel = sum(ns for _, ns in steps) / 1e9
            shares.append(100.0 * len(steps) * a_step / kernel)
            say(f"{chip.name}: flash kernels {kernel:.4f} s in "
                f"{len(steps)} whole steps against a roofline of "
                f"{len(steps) * a_step:.4f} s for [B, H, S, D] = "
                f"{list(shape)} a device")
    return sum(shares) / len(shares) if shares else None
