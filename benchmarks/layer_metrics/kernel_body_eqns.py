"""Equations in the bodies of the step's Pallas call sites
(`apex_tpu.monitor.scopes.step_kernels()`), summed over the sites: a
body called at five sites counts five times, as it is lowered."""

from benchmarks.lib import setup_ledger


def compute(observed):
    return setup_ledger.kernel_sum(observed, "body_eqns")
