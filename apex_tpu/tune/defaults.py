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
