"""Device milliseconds a step in the held experts' grouped GEMMs, all
expert layers, first device: the compiler's grouped-matmul kernels
behind `jax.lax.ragged_dot` (instructions named `ragged-dot*`: gate and
up, down, and the input and weight gradients of both) wherever they
stand under a block's `mlp`, plus what else the scope
`block*/mlp/experts` owns (SiLU and product, casts).

The kernels are found by their name and not by their owner: the
compiler drops their `op_name`, so the owner table files each under
what its users share, and the forward down-projection, whose one user
is the weighted scatter-add, under `mlp/combine`."""

from benchmarks.lib import owners

KERNEL = r"ragged-dot"


def compute(observed):
    kernels = owners.ms(observed, owner=r"block\d*/mlp(/|$)", name=KERNEL)
    if kernels is None:
        return None
    return kernels + owners.ms(observed, owner=r"block\d*/mlp/experts$",
                               but_name=KERNEL)
