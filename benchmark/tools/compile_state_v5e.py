"""``compile_groups_v5e`` for a configuration whose layers keep a state a
request beside pages a token (the round's message then carries each row's
slot): compile the round's program for a TPU v5e that is described, not
attached, and print the compiler's memory count for every token pad the
engine would warm:

    JAX_PLATFORMS=cpu python3 -m benchmark.tools.compile_state_v5e <config> [key=value ...]

It lowers the entry a round really calls, ``(arrays, message, pools)``
with the plan as one int32 message, so it serves any configuration. The
model is really built at the real widths on the CPU (constant weights, the
weights and the pools once in host memory), the Pallas backends are forced
as the start-up gate chooses them on the chip, nothing runs.
"""
import importlib
import json
import os
import sys
import time

from .probe import override


def main(argv):
    name = argv[1]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["PADDLE_TPU_SERVING_ATTN"] = "pallas"
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", name + ".json")) as f:
        cfg = json.load(f)
    for kv in argv[2:]:
        override(kv, cfg)
    import jax
    import jax.sharding as jsh
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    import paddle_tpu  # noqa: F401
    from paddle_tpu.nn import initializer as init
    from paddle_tpu.ops.pallas import _common as gate
    from paddle_tpu.serving.engine import _message_len
    fam = importlib.import_module(f"benchmark.models.{cfg['family']}")

    t0 = time.perf_counter()
    init.set_global_initializer(init.Constant(0.01), init.Constant(0.0))
    gate.on_tpu = lambda: True
    eng = fam.make_engine(fam.build_model(cfg, 0), cfg)
    gib = 2.0 ** 30
    weights = sum(a.size * a.dtype.itemsize for a in eng._param_arrays)
    state = eng.stats()["state"] or {"bytes": 0, "slots": 0}
    print(f"{name}: {cfg['num_layers']} layers, "
          f"{sum(int(a.size) for a in eng._param_arrays) / 1e9:.4f}e9 "
          f"parameters ({weights / gib:.2f} GiB), pools "
          f"{eng.kv.nbytes() / gib:.2f} GiB: page groups "
          f"{ {g.name: g.num_pages for g in eng.kv.groups} }, "
          f"{state['bytes'] / gib:.2f} GiB of state in {state['slots']} "
          f"slots; built on the CPU in {time.perf_counter() - t0:.0f} s",
          flush=True)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = jsh.SingleDeviceSharding(topo.devices[0])

    def aval(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    # the ladder warm_ragged would compile, without launching anything
    rows = max(1, eng._prefill_budget // eng.prefill_chunk)
    top, pads, t = eng.max_slots + rows * eng.prefill_chunk, [], 1
    while not pads or pads[-1] < top:
        pads.append(eng._pad(t))
        t = pads[-1] + 1
    eng._jit = False
    fn = jax.jit(eng._build_round(), donate_argnums=(2,))
    for p in pads:
        t1 = time.perf_counter()
        n = _message_len(p, eng.max_slots, eng._bt_shape(),
                         eng._state is not None)
        c = fn.lower([aval(a) for a in eng._param_arrays],
                     jax.ShapeDtypeStruct((n,), "int32", sharding=one),
                     jax.tree_util.tree_map(aval, eng.kv.pools)).compile()
        m = c.memory_analysis()
        peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(f"pad {p}: compiled in {time.perf_counter() - t1:.0f} s; "
              f"arguments {m.argument_size_in_bytes / gib:.2f} GiB (aliased "
              f"{m.alias_size_in_bytes / gib:.2f}), temporaries "
              f"{m.temp_size_in_bytes / gib:.2f}, outputs "
              f"{m.output_size_in_bytes / gib:.2f}; peak {peak / gib:.2f} "
              f"GiB of 15.75; tpu_custom_call x"
              f"{c.as_text().count('tpu_custom_call')}", flush=True)


if __name__ == "__main__":
    main(sys.argv)
