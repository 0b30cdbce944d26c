"""Committed tuned-config defaults, keyed by device kind.

These ship with the package so the benched shapes get their tuned
kernel configs out of the box — the cache file layers user sweeps on
top (cache._merged_for_kind).  Structure mirrors one device-kind
section of the cache file: {kind: {key: {"config": ..., "meta": ...}}}.

To commit defaults for a new chip: run

    python scripts/gpt_anatomy.py tune          # sweeps + writes cache

on the target hardware, then copy the winning entries from the cache
file (``apex_tpu.tune.cache_path()``) into this dict under the chip's
canonical kind (``apex_tpu.tune.device_kind()``).  ``scripts/
gpt_anatomy.py tune --check`` re-sweeps and exits nonzero when these
committed entries drift from fresh measurements.

The dense cells' flash shapes carry no entry: they take the kernels' own
blocks and, under a causal mask, the compute tiles the step bodies cut
them into (`ops/flash_attention.py::_kernel_shape`).  Each flash entry
is a shape a benchmark cell hits.
"""

from __future__ import annotations


def _flash(b, h, sq, sk, d, dtype, causal, bias="none", seg=False,
           dv=None, hkv=None):
    from apex_tpu import tune
    from apex_tpu.tune.cache import make_key
    return make_key("flash_sdpa",
                    tune.flash_attrs(b, h, sq, sk, d, dtype, causal,
                                     bias=bias, seg=seg, dv=dv, hkv=hkv))


def _mk(config, note):
    return {"config": config, "meta": {"note": note}}


# (rows, K, N, experts held, the three tilings, where they came from) of
# each expert cell's gate-and-up and down products
_GROUPED = [
    # lfm2-8b-a1b: 1,024 rows an expert
    (32768, 2048, 3584, 16, {"fwd": [256, 2048, 896],
                             "dlhs": [256, 3584, 1024],
                             "drhs": [256, 2048, 1792]},
     "v5e sweep, 16,375 rows sent: 2.36 / 2.47 / 2.48 ms "
     "(the heuristics' 3.42 / 3.41 / 3.66; ragged_dot 4.42 + 10.63)"),
    (32768, 1792, 2048, 16, {"fwd": [256, 1792, 1024],
                             "dlhs": [256, 2048, 896],
                             "drhs": [256, 1792, 1024]},
     "v5e sweep, 16,375 rows sent: 1.58 / 1.49 / 1.61 ms "
     "(the heuristics' 2.41 / 2.52 / 2.66; ragged_dot 2.25 + 6.86)"),
    # solar-open2-250b: about 102 rows an expert
    (6560, 4096, 2560, 8, {"fwd": [256, 4096, 1280],
                           "dlhs": [256, 2560, 512],
                           "drhs": [256, 4096, 512]},
     "v5e sweep, 816 rows sent: 1.02 / 1.00 / 1.19 ms (the "
     "heuristics' 1.46 / 1.39 / 1.63; ragged_dot 1.65 + 4.32)"),
    (6560, 1280, 4096, 8, {"fwd": [256, 1280, 2048],
                           "dlhs": [256, 2048, 1280],
                           "drhs": [256, 256, 512]},
     "v5e sweep, 816 rows sent: 0.83 / 0.87 / 1.18 ms (the "
     "heuristics' 1.15 / 1.18 / 1.30; ragged_dot 1.46 + 2.74)"),
    # joyai-llm-flash and kimi-linear-48b-a3b: 256 rows an expert; the
    # row tile swept, the lane tiles the heuristics' (the sweep's time
    # ran out), every time within a host call's floor of about 1 ms
    (8192, 2048, 1536, 16, {"fwd": [512, 512, 512],
                            "dlhs": [256, 512, 512],
                            "drhs": [256, 512, 512]},
     "v5e sweep, row tile only: 1.29 / 1.17 / 1.27 ms "
     "(ragged_dot 1.19 + 2.35)"),
    (8192, 768, 2048, 16, {"fwd": [256, 256, 512],
                           "dlhs": [256, 512, 256],
                           "drhs": [256, 256, 512]},
     "v5e sweep, row tile only: 1.03 / 1.06 / 1.06 ms "
     "(ragged_dot 0.95 + 1.52)"),
    (16384, 2304, 2048, 16, {"fwd": [512, 256, 512],
                             "dlhs": [256, 512, 256],
                             "drhs": [256, 256, 512]},
     "v5e sweep, row tile only: 1.66 / 1.79 / 1.85 ms "
     "(ragged_dot 1.86 + 5.38)"),
    (16384, 1024, 2304, 16, {"fwd": [256, 512, 256],
                             "dlhs": [512, 256, 512],
                             "drhs": [256, 512, 256]},
     "v5e sweep, row tile only: 1.24 / 1.12 / 1.25 ms "
     "(ragged_dot 1.28 + 2.27)"),
]


def _v5e_entries():
    """Entries measured on a v5e, each with the run it came from: a
    headline metric must never gamble on an unmeasured config.  Promote
    cache winners here per docs/tuning.md once measured."""
    from apex_tpu import tune
    from apex_tpu.tune.cache import make_key

    e = {}
    # latent attention, keys 192 and values 128, 2 x 32 heads x 4096
    # (models/mla_moe.py at the benchmark's cell): the single-pass
    # backward fits VMEM there though the one-width cap says no, and
    # (1024, 512) blocks beat the heuristics' (512, 1024)
    e[_flash(2, 32, 4096, 4096, 192, "bfloat16", True, dv=128)] = _mk(
        {"block_q": 1024, "block_k": 512, "fused_bwd": True},
        "v5e, PR 28 chip run, forward + backward a layer: 15.2 ms; "
        "fused at the heuristics' blocks 16.7, two-kernel 19.0")
    # grouped-query attention, 64 query heads on 8 kv heads of 128,
    # 1 x 4096 (models/hybrid_moe.py at the benchmark's cell)
    e[_flash(1, 64, 4096, 4096, 128, "bfloat16", True, hkv=8)] = _mk(
        {"block_q": 2048, "block_k": 512, "fused_bwd": True},
        "v5e, PR 32 chip run, forward + backward a layer: 7.53 ms; "
        "(1024, 1024) 8.00, (1024, 512) 8.44, the heuristics' (512, "
        "1024) fused 9.50 and two-kernel 12.50; (4096, 256) does not "
        "fit VMEM")
    # the chunked gated delta rule, 64 heads of 128 x 128, 1 x 4096
    # (ops/delta_rule.py at the same cell), its chunk-local stage in the
    # Pallas pair: the heads need no passes any more
    e[make_key("delta_rule", tune.delta_rule_attrs(
        1, 64, 4096, 128, 128, "bfloat16"))] = _mk(
        {"chunk": 64},
        "v5e, PR 33 chip run, forward + backward a layer: chunk 64 "
        "32.5 ms in one call of 64 heads (34.1 in two of 32), chunk 32 "
        "35.4 (38.3), chunk 128 37.2 (39.2); the compiled stage of PR "
        "32 at chunk 64 in two calls of 32 heads 75.5")
    # a row of 8,192 tokens of packed documents (models/hybrid_moe.py's
    # latent kind at the benchmark's sixth cell): latent attention's two
    # widths under the segment mask, where only the (512, 512) single
    # pass fits VMEM and two kernels at square blocks read the same;
    # and the delta rule at half the heads and twice the row of the
    # entry above, with its resets
    e[_flash(1, 32, 8192, 8192, 192, "bfloat16", True, seg=True,
             dv=128)] = _mk(
        {"block_q": 1024, "block_k": 1024, "fused_bwd": False},
        "v5e, PR 34 chip run, forward + backward a layer under the "
        "segment mask: 29.81 ms; fused at (512, 512) 29.82, two-kernel "
        "(2048, 512) 31.06, (1024, 512) 31.36, the heuristics' (512, "
        "1024) 33.18 (33.02 without segment ids), (512, 512) 35.44; a "
        "single pass at any larger block and (2048, 1024) do not fit "
        "VMEM")
    e[make_key("delta_rule", tune.delta_rule_attrs(
        1, 32, 8192, 128, 128, "bfloat16"))] = _mk(
        {"chunk": 64},
        "v5e, PR 34 chip run, forward + backward a layer with a row's "
        "resets: chunk 64 33.77 ms in one call of 32 heads (34.98 in "
        "two of 16), chunk 32 37.39 (39.77), chunk 128 38.45 (39.89); "
        "chunk 64 without resets 32.97")
    # one row of 8,192 packed tokens of the Olmo-Hybrid cell
    # (models/olmo_hybrid.py): the delta rule with one decay a head at
    # keys 96 and values 192, with its resets, and its full attention,
    # 30 heads of 128, under the segment mask
    e[make_key("delta_rule", tune.delta_rule_attrs(
        1, 30, 8192, 96, 192, "bfloat16"))] = _mk(
        {"chunk": 128, "heads": 10},
        "v5e chip run, forward + backward a layer with a row's "
        "resets: chunk 128 in three calls of 10 heads 28.81 ms, of 15 "
        "30.37, one call of 30 29.78; chunk 64 31.25-31.62; chunk 32 "
        "not swept: the step does not fit the chip at it")
    e[_flash(1, 30, 8192, 8192, 128, "bfloat16", True, seg=True)] = _mk(
        {"block_q": 2048, "block_k": 512, "fused_bwd": True},
        "v5e chip run, forward + backward a layer under the "
        "segment mask: 14.29 ms; fused (1024, 1024) 14.80, (1024, 512) "
        "16.08, (512, 512) 19.87; two kernels 18.91-23.92")
    # grouped-query attention at 64-wide heads, 32 query heads on 8 kv
    # heads, one row of 8,192 (models/shortconv_moe.py at the
    # benchmark's seventh cell): the single pass again, at the blocks of
    # the 64-on-8 entry above
    e[_flash(1, 32, 8192, 8192, 64, "bfloat16", True, hkv=8)] = _mk(
        {"block_q": 2048, "block_k": 512, "fused_bwd": True},
        "v5e, PR 39 chip run, forward + backward a layer: 14.24 ms "
        "(forward 5.28); fused (1024, 1024) 14.31, (1024, 512) 15.13, "
        "(512, 1024) 16.07, (512, 512) 17.24; two kernels (1024, 1024) "
        "19.00, (1024, 512) 20.45, (2048, 512) 21.02, the heuristics' "
        "(512, 1024) 20.53, (512, 512) 22.61")
    # the held experts' grouped GEMMs (ops/grouped_matmul.py) at the
    # four expert cells' buffers: [tm, tk, tn] a kernel, tk the product's
    # contraction; swept one axis at a time against the kernel alone
    # (scripts/grouped_matmul_sweep.py), routing seeded at the cell's
    # expected rows
    for rows, k, n, groups, config, note in _GROUPED:
        e[make_key("grouped_matmul", tune.grouped_matmul_attrs(
            rows, k, n, groups, "bfloat16"))] = _mk(config, note)
    # flat-optimizer block rows at the 1B Adam bench point: the swept
    # heuristic value, committed so the fingerprint records it
    e[make_key("opt_flat", dict(kernel="adam", rows=8388608))] = _mk(
        {"block_rows": 512},
        "v5e 1B-param sweep: 512 rows = 721 GB/s (docs/PERF.md)")
    # the same kernel over the hybrid cell's 1.295B bf16 parameters, the
    # next bucket of rows: the default read the same share of the HBM peak
    e[make_key("opt_flat", dict(kernel="adam", rows=16777216))] = _mk(
        {"block_rows": 512},
        "v5e, PR 32 chip run: adam_hbm_pct 76.9 at 512 rows, as the 1B "
        "point's 77.0")
    return e


DEFAULTS = {
    "v5e": _v5e_entries(),
}
