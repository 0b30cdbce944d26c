"""apex_tpu.tune — kernel autotuning (ISSUE 3 tentpole).

Three pieces:

  * cache   — persistent JSON config store keyed by (device kind, op,
              shape/dtype attrs); committed defaults for v5e ship in
              defaults.py; $APEX_TPU_TUNE_CACHE overrides the path,
              APEX_TPU_TUNE=0 disables all lookups.
  * tuned() — the trace-time lookup kernels call when the caller passed
              no explicit config: a pure host-side dict access (zero
              collectives, no host syncs inside jitted steps).  Returns
              None on a miss — every kernel then falls back to its
              deterministic heuristic, byte-identical to the un-tuned
              framework.
  * search  — the OFFLINE sweep driver (never times inside a jitted
              step): times candidate configs wall-clock and records the
              winners.  `scripts/gpt_anatomy.py tune` is the CLI.

Tunable surfaces wired in this round: flash attention block_q/block_k
and fused_bwd (ops/flash_attention.py::_kernel_shape), the softmax and
layer-norm row blocks (via ops._common.tuned_row_block), the flat
optimizer kernels' rows-per-block (ops/optimizer_kernels.py), and the
serving path (ISSUE 8): `flash_decode`'s kv-head packing factor (key:
decode_attrs) and the paged KV cache's page size (`serve_page`, key:
serve_page_attrs — the page IS the decode kernel's kv block, so the
one knob tunes both the DMA unit and the pool granularity), and the
MoE top-k router's row block (`moe_router`, key: moe_router_attrs —
softmax + top-k are row-independent, so the tuned blocked path is
byte-identical to the dense reference at every block size).

ISSUE 18 adds the `overlap_chunks` op (key: overlap_attrs): the chunk
count of the TP layers' fused matmul+collective pipelines and the MoE
dispatch/combine micro-chunking (parallel/overlap.py,
moe/dispatch.chunked_expert_exchange).  Heuristic 1 on a miss = the
monolithic pre-overlap program, byte-identical — chunks > 1 is a
measured-win-only setting (per device kind), because each extra chunk
pays a collective launch latency floor that only a hardware sweep can
price against the hidden bandwidth (docs/PERF.md "Chunked overlap").
"""

from apex_tpu.tune.cache import (  # noqa: F401
    ENV_CACHE_PATH,
    ENV_DISABLE,
    SCHEMA_VERSION,
    cache_path,
    device_kind,
    fingerprint,
    invalidate,
    lookup,
    make_key,
    record,
    reset_stats,
    stats,
)


def pow2_bucket(n: int) -> int:
    """Round up to the next power of two — the size coordinate of keys
    whose exact value shouldn't fragment the cache (row counts)."""
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def flash_attrs(b, h, sq, sk, d, dtype, causal, bias="none", seg=False,
                dv=None, hkv=None):
    """The ONE definition of the flash_sdpa lookup-key attrs — shared
    by the runtime lookup (ops/flash_attention.py), the sweep driver
    (tune/search.py), and the committed defaults (tune/defaults.py).
    A key-schema change here reaches all three or none.  dtype None
    means the bench dtype, bfloat16.  `dv`, v's width, is part of the
    key only where it differs from `d`: every key made before the two
    could differ reads as it did.  So with `hkv`, the kv heads of a
    grouped-query call: in the key only where they are fewer than
    `h`."""
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if dtype is None else dtype
    attrs = dict(b=int(b), h=int(h), sq=int(sq), sk=int(sk), d=int(d),
                 dtype=jnp.dtype(dtype).name, causal=bool(causal),
                 bias=bias, seg=bool(seg))
    if dv is not None and int(dv) != int(d):
        attrs["dv"] = int(dv)
    if hkv is not None and int(hkv) != int(h):
        attrs["hkv"] = int(hkv)
    return attrs


def delta_rule_attrs(b, n, s, dk, dv, dtype):
    """The ONE definition of the `delta_rule` lookup-key attrs — shared
    by the runtime lookup (ops/delta_rule.py) and the committed
    defaults.  The config carries `chunk`, the tokens a chunk of the
    chunkwise gated delta rule: the sequential part runs S / chunk
    steps and keeps as many states a head, the parallel part's work a
    token grows with the chunk.  dtype None means the bench dtype,
    bfloat16."""
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if dtype is None else dtype
    return dict(b=int(b), n=int(n), s=int(s), dk=int(dk), dv=int(dv),
                dtype=jnp.dtype(dtype).name)


def decode_attrs(n_slots, q_len, hq, hkv, d, page_size, dtype):
    """The ONE definition of the `flash_decode` lookup-key attrs —
    shared by the runtime lookup (ops/flash_decode.py), the sweep
    driver (tune/search.py), and committed defaults.  n_slots is
    pow2-bucketed: the continuous-batching engine (apex_tpu.serve)
    keeps the slot count static per deployment, but sweeps shouldn't
    fragment the cache across nearby concurrencies.  dtype None means
    the serving cache dtype, bfloat16."""
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if dtype is None else dtype
    return dict(slots=pow2_bucket(n_slots), ql=int(q_len), hq=int(hq),
                hkv=int(hkv), d=int(d), page=int(page_size),
                dtype=jnp.dtype(dtype).name)


def moe_router_attrs(tokens, n_experts, top_k, dtype):
    """The ONE definition of the `moe_router` lookup-key attrs — shared
    by the runtime lookup (moe/router.py) and any sweep driver.  The
    config carries `block_rows`, the row-block the top-k selection is
    chunked by (softmax + top_k are row-independent, so every block
    size is byte-identical to the dense reference — the tuner only
    moves the VMEM-residency/grid-overhead point).  `tokens` is
    pow2-bucketed: the local token count is batch-shape-derived and
    must not fragment the cache across nearby batch sizes.  dtype is
    the COMPUTE dtype of the incoming activations (the gate logits
    themselves are always fp32, the DP105 contract)."""
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if dtype is None else dtype
    return dict(rows=pow2_bucket(tokens), experts=int(n_experts),
                k=int(top_k), dtype=jnp.dtype(dtype).name)


def serve_page_attrs(n_kv_heads, head_dim, dtype):
    """Lookup-key attrs for the `serve_page` op — the paged-KV-cache
    page size (serve.KVCacheConfig).  The page size IS the decode
    kernel's kv block size (one page = one DMA unit), so it is keyed
    by the cache layout alone, not by concurrency."""
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if dtype is None else dtype
    return dict(hkv=int(n_kv_heads), d=int(head_dim),
                dtype=jnp.dtype(dtype).name)


def overlap_attrs(path, rows, width, axis_size, dtype):
    """The ONE definition of the `overlap_chunks` lookup-key attrs —
    shared by the runtime lookups (parallel/overlap.layer_chunks,
    moe/layer.MoEMLP) and any sweep driver.  The config carries
    `chunks`, the pipeline depth of a fused matmul+collective site.
    `path` names the site shape ("tp_col" ring-gather, "tp_row"
    GEMM+reduce-scatter, "tp_row_ar" GEMM+all-reduce, "tp_col_copy"
    backward-only dgrad psum, "moe" dispatch/combine micro-chunk);
    `rows` is the chunked dim pow2-bucketed (batch-shape-derived, must
    not fragment the cache); `width` the GEMM output width;
    `axis_size` the collective's axis size (overlap economics change
    with ring length).  dtype None means the bench dtype, bfloat16."""
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if dtype is None else dtype
    return dict(path=str(path), rows=pow2_bucket(rows), width=int(width),
                ax=int(axis_size), dtype=jnp.dtype(dtype).name)


def tuned(op: str, attrs=None, **kw):
    """Tuned config for (op, attrs) on this device kind, or None.

    attrs values must be ints/bools/strings (canonicalized into the
    cache key).  Call at TRACE time only with static shapes — the
    lookup itself touches no device state.
    """
    a = dict(attrs or {})
    a.update(kw)
    return lookup(op, a)
