"""Programs the process lowered, compiled or read from the persistent
cache before steady state was marked (the set-up ledger's count; the
jitted helpers of `jax.numpy` traced inside them are not programs)."""

from benchmarks.lib import setup_ledger


def compute(observed):
    return setup_ledger.setup_total(observed, "programs")
