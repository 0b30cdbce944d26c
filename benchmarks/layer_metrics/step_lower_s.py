"""Host seconds the registered step's jaxpr was lowered to MLIR, by the
program's own set-up ledger: `lower_s` of the records named
`jit(local_step)` before steady state was marked.  Every Pallas call
site's Mosaic lowering is in it (`kernel_body_eqns` sizes that); the
persistent cache does not hold it."""

from benchmarks.lib import setup_ledger


def compute(observed):
    return setup_ledger.step_seconds(observed, "lower_s")
