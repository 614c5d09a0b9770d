"""Compile a training configuration's whole step for a TPU v5e that is
described, not attached, and print the compiler's memory count:

    JAX_PLATFORMS=cpu python3 -m benchmark.tools.compile_v5e <config> <sequences> [num_layers]

No chip is needed and nothing runs: a compile that passes is not a chip
run. The model and the optimizer state are really built, at the real
widths, on (virtual) CPU devices through the same family builder the
benchmark uses, so the step that is traced is the benchmark's; only then
is every mesh the trace sees swapped for the described one. That takes
the host's memory: 12 bytes a parameter.

How the swap works, because the program builds its mesh from
``jax.devices()`` and places its own parameters: the eager set-up runs on
a CPU mesh of the same shape; for the trace, ``NamedSharding`` in the
program's modules maps that mesh to the described one, the hybrid group's
``mesh`` is replaced, ``on_tpu()`` answers yes (so the Pallas flash kernel
is chosen, as on the chip), and every argument is handed over as a shape
with its sharding on the described mesh.
"""
import json
import os
import sys
import time


def main(argv):
    name, sequences = argv[1], int(argv[2])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", name + ".json")) as f:
        cfg = json.load(f)
    if len(argv) > 3:
        cfg["num_layers"] = int(argv[3])
    par = cfg.get("parallel")
    chips = par["mp_degree"] * par["sharding_degree"] if par else 1
    if chips > 1:
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            f" --xla_force_host_platform_device_count={chips}"
    import jax
    import jax.numpy as jnp
    import jax.sharding as jsh
    import numpy as np
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark.models import gpt as fam
    from paddle_tpu.jit import api as jit_api
    from paddle_tpu.ops.pallas import _common as gate

    t0 = time.perf_counter()
    hcg = fam.setup_parallel(cfg)
    model = fam.build_model(cfg, 0, hcg)
    step, place = fam.make_train_step(model, cfg, hcg)
    seq = cfg["max_seq_len"]
    ids = place(np.zeros((sequences, seq), np.int32))
    labels = place(np.zeros((sequences, seq), np.int32))
    print(f"built depth {cfg['num_layers']} on the CPU in "
          f"{time.perf_counter() - t0:.0f} s", flush=True)

    # the state, as aot_compile gathers it
    params, buffers, slots, layers, opts = step._state()
    for opt in opts:
        if not opt._state_slots():
            opt.materialize()
    params, buffers, slots, layers, opts = step._state()
    arg_tensors = []
    skel = jit_api._tree_flatten(((ids, labels), ()), arg_tensors, [])
    jitted, _ = step._build_whole_step(skel, params, buffers, slots, opts,
                                       len(arg_tensors))
    print(f"state materialized at {time.perf_counter() - t0:.0f} s",
          flush=True)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    real_named = jsh.NamedSharding
    if hcg is not None:
        cpu_mesh = hcg.mesh
        tpu_mesh = jsh.Mesh(
            np.array(topo.devices[:chips]).reshape(cpu_mesh.devices.shape),
            cpu_mesh.axis_names)

        def named(mesh, spec, *a, **k):
            return real_named(tpu_mesh if mesh is cpu_mesh else mesh, spec,
                              *a, **k)

        for mod in list(sys.modules.values()):
            if getattr(mod, "__name__", "").startswith("paddle_tpu") and \
                    getattr(mod, "NamedSharding", None) is real_named:
                mod.NamedSharding = named
        hcg.mesh = tpu_mesh

        def aval(a):
            s = getattr(a, "sharding", None)
            spec = s.spec if isinstance(s, real_named) else jsh.PartitionSpec()
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=real_named(tpu_mesh, spec))
    else:
        one = jsh.SingleDeviceSharding(topo.devices[0])

        def aval(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
    gate.on_tpu = lambda: True

    state = [aval(t._data) for t in params] + \
        [aval(b._data) for b in buffers] + [aval(c[k]) for c, k in slots]
    args = [aval(t._data) for t in arg_tensors]
    small = jsh.SingleDeviceSharding(topo.devices[0]) if hcg is None \
        else real_named(tpu_mesh, jsh.PartitionSpec())
    rng = jax.ShapeDtypeStruct((3,), jnp.uint32, sharding=small)
    lrs = jax.ShapeDtypeStruct((max(len(opts), 1),), jnp.float32,
                               sharding=small)
    t1 = time.perf_counter()
    compiled = jitted.lower(state, args, rng, lrs).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    gib = 2.0 ** 30
    n_params = sum(int(np.prod(t.shape)) for t in params)
    print(f"{name} depth {cfg['num_layers']}, {sequences} x {seq} tokens, "
          f"{n_params / 1e9:.3f}e9 parameters, {chips} described v5e "
          f"chip(s): compiled in {time.perf_counter() - t1:.0f} s; per "
          f"device arguments {m.argument_size_in_bytes / gib:.2f} GiB "
          f"(aliased {m.alias_size_in_bytes / gib:.2f}), temporaries "
          f"{m.temp_size_in_bytes / gib:.2f}, outputs "
          f"{m.output_size_in_bytes / gib:.2f}; peak = arguments + "
          f"temporaries + outputs - aliased = "
          f"{(m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes) / gib:.2f}"
          f" GiB; tpu_custom_call x{text.count('tpu_custom_call')}; "
          + ", ".join(f"{op} x{text.count(op + '(') + text.count(op + '-start(')}"
                      for op in ("all-reduce", "all-gather", "reduce-scatter",
                                 "collective-permute", "all-to-all")),
          flush=True)


if __name__ == "__main__":
    main(sys.argv)
