"""Of the programs of set-up, those the persistent compilation cache
did not hold and that were compiled and written to it: 0 on a warm
start.  The `setup_ledger` line names each under `missed`."""

from benchmarks.lib import setup_ledger


def compute(observed):
    return setup_ledger.setup_total(observed, "cache_misses")
