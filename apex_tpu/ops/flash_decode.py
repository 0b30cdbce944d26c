"""Paged flash-decode attention — the serving-side counterpart of the
training flash kernel (ops/flash_attention.py, ISSUE 8 tentpole).

Decode-mode attention is a different shape class than training: ONE
query token (q_len = 1, or a handful under speculative decoding) per
sequence against a KV cache that GROWS every step, for thousands of
concurrent sequences of ragged length.  A dense (slots, max_seq)
cache would pin worst-case HBM per user; instead the cache is PAGED
(serve/kv_cache.py): a fixed pool of `(page_size, head_dim)` pages
shared by every sequence, with a per-slot block table naming which
pages hold its tokens.  The kernel gathers pages through the block
table at DMA time — the Pallas index map reads the table from SMEM
(scalar prefetch) and fetches page `block_table[slot, t]` for grid
step t — so the compiled program's shapes NEVER depend on sequence
length or concurrency churn: the continuous-batching engine
(serve/engine.py) admits and retires requests under a RecompileSentry
that proves no steady-state retrace.

Layout contract (shared with serve/kv_cache.py):

  q              (n_slots, q_len, n_q_heads, head_dim)
  k/v_pages      (n_kv_heads, n_pages, page_size, head_dim)
  block_table    (n_slots, pages_per_slot_max) int32 page ids
  lengths        (n_slots,) int32 — total visible tokens per slot,
                 INCLUDING the q_len new tokens (their K/V must
                 already be written into the pages; the engine writes
                 then attends).  0 marks an inactive slot.

Query row i of slot s sees cache positions p < lengths[s] - q_len + 1
+ i (causal within the new block); GQA rides as n_q_heads = G *
n_kv_heads with query head h reading kv head h // G.  Rows with no
visible position (inactive slots) return ZEROS — unlike the training
kernel's uniform-attention convention, a parked slot must contribute
exact zeros so the engine can keep stepping it for free.

The per-step masking is the segment-ids machinery of the training
kernel re-aimed at pages: a partial last page holds garbage beyond
`lengths` and stale table entries point at recycled pages — both are
masked by position, never by data, so the pool needs no cleaning
between requests.

heads_per_step packs that many kv heads per grid step (one shared
online-softmax epilogue, hp-head page DMAs — the same d=64 vreg-
filling axis as the training kernel's packing, PR 3) and is owned by
the apex_tpu.tune cache with a deterministic heuristic fallback.  The
kv block size IS the page size: pages are non-contiguous in the pool,
so one page is the natural DMA unit, and `page_size` itself is the
tuner-owned block-size knob (serve.KVCacheConfig consults
`tune.tuned("serve_page", ...)` when unset).

Forward-only: decode is inference — no VJP, no lse output.
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.monitor.compile.startup import kernel_span
from apex_tpu.ops._common import pallas_interpret, use_pallas

_NEG_INF = -1e30

_HP_FALLBACK_WARNED = set()


def _check_shapes(q, k_pages, v_pages, block_table, lengths):
    if q.ndim != 4:
        raise ValueError(f"q must be (n_slots, q_len, n_q_heads, "
                         f"head_dim), got {q.shape}")
    n_slots, q_len, hq, d = q.shape
    if k_pages.ndim != 4 or k_pages.shape != v_pages.shape:
        raise ValueError(
            f"k_pages/v_pages must be equal-(n_kv_heads, n_pages, "
            f"page_size, head_dim), got {k_pages.shape}/{v_pages.shape}")
    hkv = k_pages.shape[0]
    if k_pages.shape[3] != d:
        raise ValueError(f"head_dim mismatch: q {d} vs pages "
                         f"{k_pages.shape[3]}")
    if hq % hkv:
        raise ValueError(
            f"n_q_heads={hq} must be a multiple of n_kv_heads={hkv} "
            "(GQA groups)")
    if block_table.ndim != 2 or block_table.shape[0] != n_slots:
        raise ValueError(
            f"block_table must be (n_slots={n_slots}, max_pages), got "
            f"{block_table.shape}")
    if lengths.shape != (n_slots,):
        raise ValueError(
            f"lengths must be ({n_slots},), got {lengths.shape}")
    max_kv = block_table.shape[1] * k_pages.shape[2]
    if q_len > max_kv:
        raise ValueError(
            f"q_len={q_len} exceeds the table's capacity {max_kv}")


def _resolve_heads_per_step(heads_per_step, hkv, page_size):
    """Validated kv-head packing factor.  None → heuristic: the
    largest power-of-two divisor of n_kv_heads keeping the packed
    (hp · page_size) score lanes within one 1024-wide tile class (the
    same vreg-filling rationale as the training kernel's packing).
    Invalid explicit values warn once and degrade to 1 — a stale tuned
    config must never fail a serving step."""
    if heads_per_step is None:
        hp = 1
        while (hkv % (hp * 2) == 0 and (hp * 2) * page_size <= 1024
               and hp * 2 <= 16):
            hp *= 2
        return hp
    hp = int(heads_per_step)
    if hp == 1:
        return 1
    if hp < 1 or hkv % hp:
        key = ("decode_hp", hp, hkv)
        if key not in _HP_FALLBACK_WARNED:
            _HP_FALLBACK_WARNED.add(key)
            reason = ("is not positive" if hp < 1 else
                      f"does not divide n_kv_heads={hkv}")
            warnings.warn(
                f"flash_decode: heads_per_step={hp} {reason}; running "
                "unpacked", stacklevel=4)
        return 1
    return hp


def _tuned_decode_config(n_slots, q_len, hq, hkv, d, page_size, dtype):
    """Trace-time autotuner lookup (apex_tpu.tune): pure host-side
    dict access, None on a miss so an empty cache keeps the
    heuristics.  A hit is sanity-validated (hand-edited caches degrade,
    never crash a serving step)."""
    from apex_tpu import tune

    cfg = tune.tuned("flash_decode",
                     tune.decode_attrs(n_slots, q_len, hq, hkv, d,
                                       page_size, dtype))
    if not cfg:
        return None
    hp = cfg.get("heads_per_step", 1)
    if not (isinstance(hp, int) and 1 <= hp <= 16 and hkv % hp == 0):
        key = ("decode_cfg", hkv, d, page_size)
        if key not in _HP_FALLBACK_WARNED:
            _HP_FALLBACK_WARNED.add(key)
            warnings.warn(
                f"flash_decode: ignoring out-of-range tuned config "
                f"{cfg}; using heuristics", stacklevel=4)
        return None
    return cfg


# --------------------------- reference (jnp) path ---------------------------

def paged_attention_reference(q, k_pages, v_pages, block_table, lengths,
                              *, softmax_scale=None):
    """Dense paged-decode oracle: gather every table page, mask by
    position, plain softmax attention in fp32.

    Deliberately spelled with the SAME op sequence as
    flash_attention.attention_reference (einsum → where-mask →
    jax.nn.softmax → einsum → astype) so that at q_len=1 its output is
    BITWISE equal to the training path — `flash_attention` at a
    1-token query resolves to attention_reference on every backend
    (no block divides seq 1), and tests/test_serve.py pins the two
    paths together bit for bit.  Rows with no visible position return
    exact zeros (module contract)."""
    _check_shapes(q, k_pages, v_pages, block_table, lengths)
    n_slots, q_len, hq, d = q.shape
    hkv = k_pages.shape[0]
    G = hq // hkv
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / math.sqrt(d))
    # (hkv, slots, maxp, page, d) → (slots, hkv, max_kv, d)
    k = k_pages[:, block_table].transpose(1, 0, 2, 3, 4)
    v = v_pages[:, block_table].transpose(1, 0, 2, 3, 4)
    k = k.reshape(n_slots, hkv, -1, d)
    v = v.reshape(n_slots, hkv, -1, d)
    if G > 1:
        k = jnp.repeat(k, G, axis=1)
        v = jnp.repeat(v, G, axis=1)
    qb = q.transpose(0, 2, 1, 3)  # (slots, hq, q_len, d)
    s = jnp.einsum("bhqd,bhkd->bhqk", qb.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    kvpos = jnp.arange(k.shape[2], dtype=jnp.int32)[None, None, None, :]
    vis = (lengths[:, None, None, None].astype(jnp.int32) - q_len + 1
           + jnp.arange(q_len, dtype=jnp.int32)[None, None, :, None])
    s = jnp.where(kvpos >= vis, _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    # rows with zero visible positions are exact zeros, not the
    # softmax-of-all-masked uniform average
    o = jnp.where(vis > 0, o, 0.0).astype(q.dtype)
    return o.transpose(0, 2, 1, 3)


# ------------------------------ Pallas kernel -------------------------------

def _decode_kernel(lens_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, page, rows, q_len,
                   hp, n_blocks):
    """Grid (slot, kv-head group, table entry).  Scores run (hp, rows,
    page): `page` occupies the lane dim (the wide axis — rows are
    G·q_len, usually < 8), stats (hp, rows) share one epilogue across
    the packed heads.  Page blocks at or beyond `lengths[s]` are
    SKIPPED (their DMA still lands — masked by position, so stale or
    recycled page content is harmless)."""
    s = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[s]

    @pl.when(t * page < length)
    def _step():
        # per-head matmuls statically unrolled (≡ the training packed
        # kernel): bit-identical per head whatever hp is
        st = jnp.stack([
            lax.dot_general(q_ref[0, p], k_ref[p, 0],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
            for p in range(hp)]) * scale            # (hp, rows, page)
        kvpos = t * page + lax.broadcasted_iota(
            jnp.int32, (1, rows, page), 2)
        ridx = lax.broadcasted_iota(jnp.int32, (1, rows, page), 1)
        vis = length - q_len + 1 + (ridx % q_len)   # causal in-block
        st = jnp.where(kvpos >= vis, _NEG_INF, st)
        m_prev = m_scr[...]                         # (hp, rows)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=2))
        p_exp = jnp.exp(st - m_new[:, :, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p_exp, axis=2)
        acc_scr[...] = acc_scr[...] * alpha[:, :, None] + jnp.stack([
            lax.dot_general(p_exp[p].astype(v_ref.dtype), v_ref[p, 0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
            for p in range(hp)])                    # (hp, rows, d)
        m_scr[...] = m_new

    @pl.when(t == n_blocks - 1)
    def _epilogue():
        l = jnp.maximum(l_scr[...], 1e-30)          # (hp, rows)
        o = acc_scr[...] / l[:, :, None]
        # zero-visibility rows (inactive slots; q rows before the
        # sequence start) are exact zeros, per the module contract
        ridx = lax.broadcasted_iota(jnp.int32, (hp, rows), 1)
        rvalid = (length - q_len + 1 + (ridx % q_len)) > 0
        o_ref[...] = jnp.where(rvalid[:, :, None], o,
                               0.0).astype(o_ref.dtype)[None]


def _decode_pallas(q, k_pages, v_pages, block_table, lengths, scale, hp):
    n_slots, q_len, hq, d = q.shape
    hkv, _, page, _ = k_pages.shape
    G = hq // hkv
    rows = G * q_len
    max_pages = block_table.shape[1]
    hg = hkv // hp
    # rows grouped per kv head: row r = g·q_len + i (g = in-group q
    # head, i = q position)
    qr = q.transpose(0, 2, 1, 3).reshape(n_slots, hkv, rows, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # lengths, block_table (SMEM)
        grid=(n_slots, hg, max_pages),
        in_specs=[
            pl.BlockSpec((1, hp, rows, d),
                         lambda s, g, t, lens, tbl: (s, g, 0, 0)),
            # the paged gather: page block_table[s, t] is DMA'd for
            # grid step t — the block index map IS the gather
            pl.BlockSpec((hp, 1, page, d),
                         lambda s, g, t, lens, tbl: (g, tbl[s, t], 0, 0)),
            pl.BlockSpec((hp, 1, page, d),
                         lambda s, g, t, lens, tbl: (g, tbl[s, t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hp, rows, d),
                               lambda s, g, t, lens, tbl: (s, g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((hp, rows), jnp.float32),
                        pltpu.VMEM((hp, rows), jnp.float32),
                        pltpu.VMEM((hp, rows, d), jnp.float32)],
    )
    with kernel_span("flash_decode"):
        out = pl.pallas_call(
            functools.partial(_decode_kernel, scale=scale, page=page,
                              rows=rows, q_len=q_len, hp=hp,
                              n_blocks=max_pages),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_slots, hkv, rows, d), q.dtype),
            # the table axis carries the online-softmax recurrence and must
            # stay sequential; slot and head-group own disjoint outputs
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=pallas_interpret(),
            name="flash_decode",
        )(lengths.astype(jnp.int32), block_table.astype(jnp.int32),
          qr, k_pages, v_pages)
    return (out.reshape(n_slots, hkv, G, q_len, d)
            .transpose(0, 3, 1, 2, 4).reshape(n_slots, q_len, hq, d))


# --------------------------------- public API -------------------------------

def flash_decode(q, k_pages, v_pages, block_table, lengths, *,
                 softmax_scale: Optional[float] = None,
                 heads_per_step: Optional[int] = None,
                 use_pallas_override: Optional[bool] = None):
    """Single/few-query attention against a paged KV cache.

    See the module docstring for the layout contract.  heads_per_step
    None consults the apex_tpu.tune cache at trace time (key:
    `tune.decode_attrs`) and falls back to the deterministic heuristic
    on a miss — an empty cache is byte-identical to the un-tuned
    kernel.  Inactive slots (lengths == 0) return exact zeros.

    The Pallas path runs on TPU (or under APEX_TPU_FORCE_PALLAS=1 /
    override=True in interpret mode); elsewhere the dense gathered
    reference runs — at q_len=1 that path is bitwise-identical to
    `flash_attention` over the gathered cache (tests/test_serve.py).
    """
    _check_shapes(q, k_pages, v_pages, block_table, lengths)
    n_slots, q_len, hq, d = q.shape
    hkv, _, page, _ = k_pages.shape
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / math.sqrt(d))
    if not use_pallas(use_pallas_override):
        return paged_attention_reference(
            q, k_pages, v_pages, block_table, lengths,
            softmax_scale=scale)
    if heads_per_step is None:
        cfg = _tuned_decode_config(n_slots, q_len, hq, hkv, d, page,
                                   q.dtype)
        if cfg:
            heads_per_step = cfg.get("heads_per_step")
    hp = _resolve_heads_per_step(heads_per_step, hkv, page)
    return _decode_pallas(q, k_pages, v_pages, block_table, lengths,
                          scale, hp)
