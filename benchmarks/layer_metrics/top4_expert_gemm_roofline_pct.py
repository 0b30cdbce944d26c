"""The held experts' grouped GEMMs' share of their roofline: the least
time the chip could take for them, the larger of required FLOPs over
peak FLOP/s and required bytes over peak bytes/s at the assignments
uniform routing sends (benchmarks/lib/work_shortconv_moe.py::
expert_gemm_work; at 1,024 rows an expert the FLOPs are the bound),
over `top4_expert_gemm_ms`."""

from benchmarks.layer_metrics import top4_expert_gemm_ms


def compute(observed):
    took = top4_expert_gemm_ms.compute(observed)
    work = observed.get("work", {}).get("expert_gemm")
    if not (took and work and observed.get("peaks")):
        return None
    peaks = observed["peaks"]
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least / took
