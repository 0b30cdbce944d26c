"""Compiles the RecompileSentry counted after warm-up.  Anything but 0
also makes the run not `correct`."""


def compute(observed):
    return observed["counters"].get("steady_recompiles")
