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

The v5e flash entries below pack 2 heads per grid step (heads_per_step)
with 512-square blocks: the d=64 per-head score block is VPU-epilogue
and grid-overhead bound (docs/PERF.md roofline: 29–44% of the 7-matmul
mix ceiling), and packing fills the softmax-stat vregs across heads
while keeping the (hp·bk·bq) fp32 score tile at 2 MB of VMEM.  Those
are shapes no benchmark cell runs: the cells' shapes take the kernels'
own blocks and, under a causal mask, the compute tiles the step bodies
cut them into (`ops/flash_attention.py::_pick_tile`); the one entry a
cell does hit is the latent-attention shape at the end.
"""

from __future__ import annotations


def _flash(b, h, sq, sk, d, dtype, causal, bias="none", seg=False,
           dv=None):
    from apex_tpu import tune
    from apex_tpu.tune.cache import make_key
    return make_key("flash_sdpa",
                    tune.flash_attrs(b, h, sq, sk, d, dtype, causal,
                                     bias=bias, seg=seg, dv=dv))


def _mk(config, note):
    return {"config": config, "meta": {"note": note}}


def _v5e_entries():
    """Only the ATTENTION-KERNEL bench shapes carry packed defaults so
    far — the shapes bench.py measures inside per-metric try/except
    blocks (mha_latencies, long_context) and the ISSUE 3 acceptance
    shape (GPT-1.3B seq-2048, `gpt_anatomy.py roofline 1p3b2k`).  The
    MODEL-step shapes (GPT-350M b12 s1024, 1.3B b7 s512, BERT b32
    s512) deliberately stay on heuristics until a hardware sweep
    (`gpt_anatomy.py tune`) confirms the packed kernel's Mosaic
    compile + win there — the headline bench metrics must never gamble
    on an unmeasured config.  Promote cache winners here per
    docs/tuning.md once measured."""
    note = ("committed v5e default (attention bench shapes); refresh "
            "with scripts/gpt_anatomy.py tune")
    pack2 = {"block_q": 512, "block_k": 512, "heads_per_step": 2}
    e = {}
    # GPT-1.3B seq-2048 (b4 h32 d64 causal): the d=64 plateau shape
    # ISSUE 3's acceptance criterion measures via roofline
    e[_flash(4, 32, 2048, 2048, 64, "bfloat16", True)] = _mk(pack2, note)
    # MHA bench point: b8 h16 s2048 d64 causal (bench.py _mha_latencies)
    e[_flash(8, 16, 2048, 2048, 64, "bfloat16", True)] = _mk(pack2, note)
    # long-context 32k: b1 h8 s32768 d64 causal (bench.py); blocks stay
    # within the sweep's own hp*bq*bk <= 512k score-tile cap
    e[_flash(1, 8, 32768, 32768, 64, "bfloat16", True)] = _mk(pack2, note)
    # latent attention, keys 192 and values 128, 2 x 32 heads x 4096
    # (models/mla_moe.py at the benchmark's cell): the single-pass
    # backward fits VMEM there though the one-width cap says no, and
    # (1024, 512) blocks beat the heuristics' (512, 1024)
    e[_flash(2, 32, 4096, 4096, 192, "bfloat16", True, dv=128)] = _mk(
        {"block_q": 1024, "block_k": 512, "fused_bwd": True},
        "v5e, PR 28 chip run, forward + backward a layer: 15.2 ms; "
        "fused at the heuristics' blocks 16.7, two-kernel 19.0")
    # flat-optimizer block rows at the 1B Adam bench point: the swept
    # heuristic value, committed so the fingerprint records it
    from apex_tpu.tune.cache import make_key
    e[make_key("opt_flat", dict(kernel="adam", rows=8388608))] = _mk(
        {"block_rows": 512},
        "v5e 1B-param sweep: 512 rows = 721 GB/s (docs/PERF.md)")
    return e


DEFAULTS = {
    "v5e": _v5e_entries(),
}
