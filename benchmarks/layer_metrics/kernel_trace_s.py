"""Host seconds in the kernel spans (`startup.kernel_span`, around each
binding of a `pl.pallas_call` of `apex_tpu/ops`, where its body is
traced) while the registered step's programs were traced, all kernels:
the share of `step_trace_s` that is kernel bodies."""

from benchmarks.lib import setup_ledger


def compute(observed):
    found = setup_ledger.read(observed)
    if found is None:
        return None
    return sum(cell["trace_s"]
               for cell in found["step_kernel_spans"].values())
