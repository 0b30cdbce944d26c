"""Host seconds the registered step's program was traced, by the
program's own set-up ledger (`apex_tpu.monitor.compile.startup`):
`trace_s` of the records named `jit(local_step)` before steady state
was marked.  Python running the model, the kernels' bodies among it
(`kernel_trace_s` is that share); the persistent cache does not hold
it.  With `step_lower_s`, what `trace_lower_s` takes from outside."""

from benchmarks.lib import setup_ledger


def compute(observed):
    return setup_ledger.step_seconds(observed, "trace_s")
