"""Share of the traced stretch in which the device is idle and the serve
loop is NOT waiting for work (``serve.idle_wait``): the idle the host
causes. ``device_idle_pct.serve`` minus this is the empty queue. Also
prints the device's idle seconds by host phase and the share of them that
some span covers (``benchmark/host_trace.py``)."""
from benchmark import host_trace
from benchmark.harness import say

LAYER = "device"
MOVES = "itl_p99_ms"


def read(run):
    ht = host_trace.of_run(run)
    by_phase = host_trace.idle_by_phase(ht) if ht is not None else None
    if not by_phase:
        return None
    idle = sum(by_phase.values())
    if ht.lag_bounds is None:
        say("clocks: the runtime recorded no enqueue or completion events, "
            "so the device's and the host's are taken as one")
    else:
        low, up = (x / 1e6 for x in ht.lag_bounds)
        say(f"clocks: the device's runs {low:.3f} to {up:.3f} ms behind the "
            "host's (a program starts after its enqueue and ends before its "
            f"completion is heard), {ht.lag_ns / 1e6:.3f} applied")
    say("device idle seconds by host phase: " + ", ".join(
        f"{name} {s:.4f}" for name, s in
        sorted(by_phase.items(), key=lambda kv: -kv[1]))
        + f"; {idle:.4f} in all over a stretch of {ht.window_ns / 1e9:.3f}")
    covered = idle - by_phase.get(host_trace.UNATTRIBUTED, 0.0)
    say(f"named spans cover {100.0 * covered / idle:.2f} % of the device's "
        "idle time in the stretch" if idle else
        "the device was never idle in the stretch")
    waiting = by_phase.get(host_trace.IDLE_WAIT, 0.0)
    return 100.0 * (idle - waiting) / (ht.window_ns / 1e9)
