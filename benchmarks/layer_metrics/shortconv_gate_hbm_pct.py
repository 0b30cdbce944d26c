"""The gated short convolution's share of the HBM peak: the least time
the chip could take for the bytes its gates and taps have to move a
step (benchmarks/lib/work_shortconv_moe.py::short_conv_work: forward
3H read and H written, backward 4H read and 3H written a token and
layer) over `shortconv_gate_ms`.  Bandwidth is the bound: the work is a
dozen FLOPs a channel and token."""

from benchmarks.layer_metrics import shortconv_gate_ms


def compute(observed):
    took = shortconv_gate_ms.compute(observed)
    work = observed.get("work", {}).get("short_conv")
    if not (took and work and observed.get("peaks")):
        return None
    least = work["bytes"] / observed["peaks"]["hbm_bytes_per_s"]
    return 100.0 * 1e3 * least / took
