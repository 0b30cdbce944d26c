"""Percent of this run's traced `stage_conv_heads` calls that took the
Pallas pair `conv_stage` / `conv_unstage`
(`apex_tpu.ops.conv_stage.stats()`: 100 x kernel_calls / calls, counted
while tracing: the step's and the checks' before it).  100 on the chip
at the cells' 128-wide heads; 0 says every call took the compiled
`jax.numpy` body.  None on a program that has no such op, or that
traced no call."""


def compute(observed):
    try:
        from apex_tpu.ops import conv_stage
    except ImportError:
        return None
    calls = conv_stage.stats()
    if not calls["calls"]:
        return None
    return 100.0 * calls["kernel_calls"] / calls["calls"]
