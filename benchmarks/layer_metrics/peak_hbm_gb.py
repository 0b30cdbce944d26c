"""The most the fullest device held, in GB: the larger of the arrays'
own peak (`memory_stats()["peak_bytes_in_use"]`, which cannot be reset,
so the correctness check is kept far under the step's footprint) and
arrays plus the loaded step's reserved scratch (`bytes_in_use` +
`bytes_reserved`) at the end of the window."""


def compute(observed):
    peak = max(observed["peak_bytes"], default=0)
    return peak / 1e9 if peak else None
