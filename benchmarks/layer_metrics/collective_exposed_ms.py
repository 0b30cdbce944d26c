"""Milliseconds a step in which a collective runs on the first device
and no other operation does: the part of the communication that is
paid."""


def compute(observed):
    reduced = observed.get("trace")
    if not reduced:
        return None
    first = reduced["first"]
    return 1e3 * first["exposed_collective_s"] / first["n_steps"]
