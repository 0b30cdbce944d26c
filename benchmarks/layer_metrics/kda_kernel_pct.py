"""Percent of this run's traced `gated_delta_rule` calls whose chunk-
local stage took the Pallas pair `kda_locals_fwd` / `kda_locals_bwd`
(`apex_tpu.ops.delta_rule.stats()`: 100 x kernel_calls / calls, counted
while tracing).  100 on the chip at the cell's 128-wide heads; 0 says
every call took the compiled `jax.numpy` stage.  None on a program that
has no such counter, or that traced no call."""


def compute(observed):
    from apex_tpu.ops import delta_rule

    calls = delta_rule.stats()
    if "kernel_calls" not in calls or not calls["calls"]:
        return None
    return 100.0 * calls["kernel_calls"] / calls["calls"]
