"""Model FLOP/s utilization: the FLOPs the forward and backward require
for a token (benchmarks/lib/work.py: causal attention at half, nothing
recomputed) times this run's tokens a second, over the chips' bf16
peak.  An end-to-end utilization, not a kernel's roofline share."""


def compute(observed):
    if not observed.get("peaks"):
        return None
    achieved = observed["work"]["flops_per_token"] * observed["tokens_per_s"]
    return 100.0 * achieved / (
        observed["chips"] * observed["peaks"]["bf16_flops_per_s"])
