"""Host seconds of tracing, lowering and compiling (or reading from the
persistent cache) every program of set-up but the registered step:
the weights, the batches, the checks, the optimizer's state.  The
ledger's exact total before steady state less the step's records."""

from benchmarks.lib import setup_ledger


def compute(observed):
    found = setup_ledger.read(observed)
    return None if found is None else found["setup_s"] - found["step_s"]
