"""Device milliseconds a step owned by the multi-token-prediction
module, forward and backward, first device: its own scopes (`mtp`,
`mtp/proj`, `mtp/head`: input norms and projection, final norm, the
shared head's second pass and its loss) and its block, which the
program numbers after the main model's (`counters["mtp_block"]`)."""

from benchmarks.lib import owners


def compute(observed):
    block = observed.get("counters", {}).get("mtp_block")
    if block is None:
        return None
    return owners.ms(observed, owner=rf"(mtp(/|$)|block{block}/)")
