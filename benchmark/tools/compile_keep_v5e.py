"""``compile_v5e`` with the recompute policy's memory probe answered for the
described chip: which tensors the blocks would keep on a v5e, and what the
TPU compiler counts for that program, before any chip time is spent:

    JAX_PLATFORMS=cpu python3 -m benchmark.tools.compile_keep_v5e <config> <sequences> [num_layers] [--limit-gib 15.75] [--text <file>]

On the CPU the program's probe (``fleet/recompute.py`` ``device_free_bytes``)
reports no memory and the blocks keep nothing, which is what
``compile_v5e`` compiles. Here the probe answers ``--limit-gib`` (a v5e's
``bytes_limit``, 15.75 GiB) minus what the first CPU device holds when the
step is traced: the parameters, masters and moments of one device's share,
as on the chip. Everything else is ``compile_v5e``'s own ``main``, so the
step, the mesh swap and the printed count are the benchmark's. The
``recompute.keep`` event the trace recorded is printed after it. A lower
``--limit-gib`` shows what a fuller device would keep. ``--text`` writes the
compiled step's text to a file (``compile_v5e`` only counts words in it).
"""
import json
import sys


def _option(argv, name, default):
    if name not in argv:
        return default, argv
    at = argv.index(name)
    return argv[at + 1], argv[:at] + argv[at + 2:]


def main(argv):
    limit_gib, argv = _option(argv, "--limit-gib", 15.75)
    limit_gib = float(limit_gib)
    text_file, argv = _option(argv, "--text", None)
    import jax
    from benchmark.tools import compile_v5e
    from paddle_tpu.models import gpt
    from paddle_tpu.observability import tracing

    def free_bytes():
        dev = jax.devices()[0]
        held = sum(s.data.nbytes for a in jax.live_arrays()
                   for s in a.addressable_shards if s.device == dev)
        print(f"probe: limit {limit_gib:.2f} GiB, held on {dev} "
              f"{held / 2.0 ** 30:.2f} GiB", flush=True)
        return int(limit_gib * 2 ** 30) - held

    gpt.device_free_bytes = free_bytes
    if text_file:
        as_text = jax.stages.Compiled.as_text

        def keep_text(self, *a, **k):
            text = as_text(self, *a, **k)
            with open(text_file, "w") as f:
                f.write(text)
            return text

        jax.stages.Compiled.as_text = keep_text
    events = tracing.start()
    compile_v5e.main(argv)
    for ev in events.events:
        if ev["name"] == "recompute.keep":
            print("recompute.keep " + json.dumps(ev["args"]), flush=True)
    tracing.stop()


if __name__ == "__main__":
    main(sys.argv)
