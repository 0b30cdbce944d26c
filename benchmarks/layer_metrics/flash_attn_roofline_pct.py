"""The flash kernels' share of their roofline: the least time the chip
could take for the attention a step requires — the larger of required
FLOPs over peak FLOP/s and required bytes over peak bytes/s
(benchmarks/lib/work.py) — over the time the kernels took."""

from benchmarks.lib import trace


def compute(observed):
    took = trace.kernel_seconds_per_step(observed, "flash")
    if not (took and observed.get("peaks")):
        return None
    flash, peaks = observed["work"]["flash"], observed["peaks"]
    least = max(flash["flops"] / peaks["bf16_flops_per_s"],
                flash["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
