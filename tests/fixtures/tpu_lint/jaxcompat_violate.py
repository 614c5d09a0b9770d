"""tpu-lint fixture: deprecated jax.experimental spellings the installed jax dropped."""
from jax.experimental.shard_map import shard_map  # JC001
from jax.experimental import enable_x64  # JC003


def build(mesh, impl, spec):
    # JC002: the removed kwarg; jax.shard_map raises TypeError on it
    return shard_map(impl, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_rep=False)
