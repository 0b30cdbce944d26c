"""Host seconds in `step.lower(...)`: tracing the model in Python and
lowering it.  Paid by every run; the compile cache does not hold it."""


def compute(observed):
    return observed["spans"].get("trace_lower_s")
