"""tpu-lint fixture: the installed jax's spellings — zero findings expected."""
import jax
from jax import shard_map


def build(mesh, impl, spec):
    return shard_map(impl, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)


def with_x64():
    with jax.enable_x64():
        return jax.numpy.arange(3)
