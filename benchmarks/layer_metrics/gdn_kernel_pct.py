"""Percent of this run's traced `gated_delta_rule` calls with one decay
a head whose chunk-local stage took the Pallas pair `kda_locals_fwd` /
`kda_locals_bwd` (`apex_tpu.ops.delta_rule.stats()`: 100 x
scalar_kernel_calls / scalar_calls, counted while tracing).  100 on the
chip; 0 says every such call took the compiled `jax.numpy` stage.
None on a program that has no such counter, or that traced no such
call."""


def compute(observed):
    from apex_tpu.ops import delta_rule

    calls = delta_rule.stats()
    if not calls.get("scalar_calls"):
        return None
    return 100.0 * calls["scalar_kernel_calls"] / calls["scalar_calls"]
