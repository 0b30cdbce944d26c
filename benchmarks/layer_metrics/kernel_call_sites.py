"""Pallas call sites in the traced jaxpr of the step that ran
(`apex_tpu.monitor.scopes.step_kernels()`), all kernels: forward,
recomputed forward and backward each count, a site in a scanned body
once.  Each is lowered by Mosaic on its own."""

from benchmarks.lib import setup_ledger


def compute(observed):
    return setup_ledger.kernel_sum(observed, "call_sites")
