"""The scalar-decay delta rule's share of its roofline: the least time
the chip could take for the recurrence a step requires — the larger of
required FLOPs over peak FLOP/s and required bytes over peak bytes/s
(`benchmarks/lib/work_olmo_hybrid.py::scan_work`: the recurrence a
token at a time over every Gated DeltaNet layer's heads and the row's
tokens, inputs and gradients through HBM once; the bytes decide) —
over `gdn_scan_ms`, the time everything under `block*/attn/scan` took."""

from benchmarks.layer_metrics import gdn_scan_ms


def compute(observed):
    took = gdn_scan_ms.compute(observed)
    scan = observed.get("work", {}).get("scan")
    if not (took and scan and observed.get("peaks")):
        return None
    peaks = observed["peaks"]
    least = max(scan["flops"] / peaks["bf16_flops_per_s"],
                scan["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least / took
