"""The share of the steady traced window in which no operation ran, on
the device where that share is largest."""


def compute(observed):
    reduced = observed.get("trace")
    return reduced["idle_pct"] if reduced else None
