"""Blockwise flash attention — Pallas fwd + bwd, the core attention kernel.

≡ the reference's largest kernel investments combined:
  * fmhalib — fixed-size flash-style fused MHA, seq ≤ 512, sm80/90
    (apex/contrib/csrc/fmha/, 7.0k LoC CUDA)
  * fast_multihead_attn — fused MHA variants w/ cutlass GEMMs + fused
    softmax (apex/contrib/csrc/multihead_attn/, 7.9k LoC CUDA)
re-designed as ONE blockwise kernel with no sequence-length cap: online
softmax (running max/denominator) tiles (bq × bk) score blocks through
VMEM so the (sq × sk) score matrix never reaches HBM.  The backward
recomputes scores blockwise (flash-attention-2 style: dq in one grid,
dk/dv in another) from the saved logsumexp.

The blockwise structure is deliberately ring-friendly: a context-
parallel extension rotates K/V blocks over ICI between the same
per-block inner steps (SURVEY §2.4 CP note).

Layout: (batch, heads, seq, head_dim); head_dim padded to the 128-lane
tile inside the kernel when needed.
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

from apex_tpu.ops._common import dropout as _dense_dropout
from apex_tpu.ops._common import pallas_interpret, use_pallas

_NEG_INF = -1e30
# fused single-pass backward cap: full-(sk, d) dk/dv scratch must fit
# VMEM (tests monkeypatch this to force the two-kernel path at small sizes)
_FUSED_BWD_CAP = 256 * 1024
# head-packed fused backward: TOTAL (hp, sk, d) scratch cap — two fp32
# scratches at this size are 4 MB of VMEM; beyond it the backward drops
# to hp=1 (fused or two-kernel as before)
_FUSED_BWD_CAP_PACKED = 512 * 1024


def _causal_dispatch(step_fn, j, t, bq, bk, causal):
    """Run step_fn(masked) gated on the causal block structure: skip
    blocks above the diagonal entirely; apply mask arithmetic only on
    diagonal-crossing blocks (interior blocks take the unmasked path —
    the per-score iota/compare/select chain is a large share of VPU
    time)."""
    if not causal:
        step_fn(False)
        return
    on_diag = (t * bk + bk - 1) > (j * bq)
    run = (t * bk) <= (j * bq + bq - 1)
    pl.when(run & on_diag)(lambda: step_fn(True))
    pl.when(run & jnp.logical_not(on_diag))(lambda: step_fn(False))


def _causal_mask(st, j, t, bq, bk):
    """Mask scores above the diagonal on a TRANSPOSED (bk, bq) block."""
    krow = t * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    qcol = j * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    return jnp.where(krow > qcol, _NEG_INF, st)


def _mask_bias(st, j, t, bq, bk, causal_masked, bias_kind, bias_ref,
               has_seg, qseg_ref, kseg_ref):
    """Apply (in order) additive bias, segment mask, causal mask to a
    TRANSPOSED (bk, bq) score block.

    ≡ the reference's additive-mask softmax fusion
    (apex/contrib/csrc/multihead_attn/softmax.cuh:27-200 computes
    x*scale + mask in-kernel) and the fmha varlen packing
    (fmha_api.cpp:18-160's cu_seqlens): segment ids are the TPU-native
    varlen — tokens attend only within equal ids, so packed sequences
    and padding cost no cross-attention.

    bias_kind: "none" | "full" (a transposed (bk, bq) block of a
    (.., sq, sk) bias) | "sk" (a (.., 1, sk) key-compact bias riding as
    a (bk,) row — padding masks / ALiBi never expand to S² in HBM)."""
    if bias_kind == "full":
        st = st + bias_ref[0, 0]                        # (bk, bq)
    elif bias_kind == "sk":
        st = st + bias_ref[0, 0, 0].reshape(bk, 1)      # k-varying row
    if has_seg:
        qs = qseg_ref[0, j]                             # (bq,) lanes
        ks = kseg_ref[0, t].reshape(bk, 1)              # (bk, 1) sublanes
        st = jnp.where(ks != qs, _NEG_INF, st)
    if causal_masked:
        st = _causal_mask(st, j, t, bq, bk)
    return st


def _bias_kind(bias, sk):
    """Static bias classification.  "sk" = key-compact (.., 1, sk):
    rides compact through the kernels (no S² expansion in HBM — the
    padding-mask / ALiBi case).  "none" also covers query-compact
    (.., *, 1) biases: a per-query score constant cancels exactly in
    softmax (finite values — whole-row masking must use segment ids),
    so the kernels skip it entirely instead of expanding it to S².
    Everything else is "full" (.., sq, sk)."""
    if bias is None:
        return "none"
    if bias.shape[3] == 1:
        return "none"
    if bias.shape[2] == 1 and bias.shape[3] == sk:
        return "sk"
    return "full"


def _extras_arrays(b, h, sq, sk, nq, bq, nk, bk, bias, q_seg, kv_seg,
                   bias_kind="none"):
    """Host-side packing of the optional bias / segment-id operands.

    bias: broadcastable (nb in {1,b}, nh in {1,h}, sq, sk) — "full"
    biases pass to the kernels TRANSPOSED as (nb, nh, sk, sq) so score
    blocks need no per-step transpose; "sk" key-compact biases stay
    (nb, nh, 1, sk) — never expanded.  Segment ids: (b, s) int32,
    reshaped to (b, n_blocks, block) whole-row-resident blocks.  Absent
    operands ride as (1,1,1,1)/(1,1,1) dummies (static kind flags gate
    every kernel read)."""
    if bias_kind == "sk":
        nb, nh = bias.shape[0], bias.shape[1]
        bias_t = bias.astype(jnp.float32)               # (nb, nh, 1, sk)
    elif bias_kind == "full":
        nb, nh = bias.shape[0], bias.shape[1]
        # broadcast-1 sq dims expand HERE (inside fwd/bwd impls, not
        # before the custom_vjp) so the VJP residuals keep the caller's
        # compact bias; batch/head broadcasting stays in the index map
        bias_t = jnp.broadcast_to(
            jnp.swapaxes(bias.astype(jnp.float32), 2, 3),
            (nb, nh, sk, sq))
    else:
        nb = nh = 1
        bias_t = jnp.zeros((1, 1, 1, 1), jnp.float32)
    if q_seg is not None:
        qs = q_seg.astype(jnp.int32).reshape(b, nq, bq)
        ks = kv_seg.astype(jnp.int32).reshape(b, nk, bk)
    else:
        qs = jnp.zeros((1, 1, 1), jnp.int32)
        ks = jnp.zeros((1, 1, 1), jnp.int32)
    return bias_t, qs, ks


def _extras_specs(h, nq, bq, nk, bk, bias_kind, nb, nh, has_seg, *,
                  jt_from_args, hp=1):
    """BlockSpecs for (bias_t, q_seg, kv_seg).  `jt_from_args` maps the
    grid args after i to (j, t) — grids differ in block order.

    With head packing (hp > 1, hp | h) grid axis 0 indexes GROUPS of hp
    consecutive heads: i = batch * (h/hp) + head_group, so the batch
    index becomes i // (h/hp) and a per-head ("full"/"sk" with nh > 1)
    bias rides as an hp-tall head block.  At hp == 1 every map below is
    exactly the unpacked one."""
    hg = h // hp   # head groups per batch (grid-axis-0 stride)
    if bias_kind == "full":
        def bias_idx(i, *rest):
            j, t = jt_from_args(*rest)
            return (i // hg if nb > 1 else 0,
                    i % hg if nh > 1 else 0, t, j)
        bspec = pl.BlockSpec((1, hp if nh > 1 else 1, bk, bq), bias_idx)
    elif bias_kind == "sk":
        def bias_idx(i, *rest):
            j, t = jt_from_args(*rest)
            return (i // hg if nb > 1 else 0,
                    i % hg if nh > 1 else 0, 0, t)
        bspec = pl.BlockSpec((1, hp if nh > 1 else 1, 1, bk), bias_idx)
    else:
        bspec = pl.BlockSpec((1, 1, 1, 1), lambda i, *_: (0, 0, 0, 0))
    if has_seg:
        qspec = pl.BlockSpec((1, nq, bq), lambda i, *_: (i // hg, 0, 0))
        kspec = pl.BlockSpec((1, nk, bk), lambda i, *_: (i // hg, 0, 0))
    else:
        qspec = pl.BlockSpec((1, 1, 1), lambda i, *_: (0, 0, 0))
        kspec = pl.BlockSpec((1, 1, 1), lambda i, *_: (0, 0, 0))
    return bspec, qspec, kspec


def _fmix32(h):
    """murmur3 finalizer: full avalanche over int32 lanes."""
    h = h ^ lax.shift_right_logical(h, 16)
    h = h * jnp.int32(-2048144789)        # 0x85ebca6b
    h = h ^ lax.shift_right_logical(h, 13)
    h = h * jnp.int32(-1028477387)        # 0xc2b2ae35
    h = h ^ lax.shift_right_logical(h, 16)
    return h


def _dropout_keep(seed_ref, i, j, t, shape, rate):
    """Deterministic per-score-block keep mask from a COORDINATE hash.

    ≡ the reference FMHA's philox dropout (apex/contrib/csrc/fmha/src/
    fmha/softmax.h): counter-based bits so the BACKWARD kernels
    regenerate the identical mask without storing sq x sk bytes.  The
    bits are a murmur-style hash of (seed, head, GLOBAL score
    coordinates) — a pure function of the element's identity, so any
    kernel (any grid order, any block size, interpret mode included)
    reproduces it exactly.  seed_ref rows 1 and 2 carry the chunk's
    global (q, k) sequence offsets: a ring-attention chunk covering
    global rows [q_off, q_off+s) x [k_off, k_off+s) generates the SAME
    bits as single-chip attention over the gathered sequence, so
    dropout composes across ring steps (fwd and bwd see one mask).
    The hardware PRNG (pltpu.prng_random_bits) is NOT usable here: its
    stream→element mapping follows each kernel's codegen, so forward
    and backward kernels with different structure silently disagree."""
    bk, bq = shape
    krow = (seed_ref[2, 0] + t * bk
            + lax.broadcasted_iota(jnp.int32, shape, 0))  # k global
    qcol = (seed_ref[1, 0] + j * bq
            + lax.broadcasted_iota(jnp.int32, shape, 1))  # q global
    h = seed_ref[0, 0] * jnp.int32(1000003) + jnp.int32(i)
    v = (h + krow * jnp.int32(-1640531535)       # 0x9e3779b1
         + qcol * jnp.int32(-2048144777))        # 0x85ebca77
    v = _fmix32(v)
    # integer-only compare (Mosaic has no uint32->f32 cast): clear the
    # sign bit for a uniform int32 in [0, 2^31) and threshold against
    # rate * 2^31
    r = v & jnp.int32(0x7FFFFFFF)
    thresh = jnp.int32(int(rate * 2147483648.0))
    return r >= thresh


def _seed3(seed, q_off=0, k_off=0):
    """(3, 1) int32 seed operand: [seed, global q offset, global k
    offset].  Accepts None, scalars, or legacy (1, 1) seed arrays;
    offsets may be traced (ring steps pass rank/src-dependent values)."""
    if seed is None:
        seed = jnp.zeros((), jnp.int32)
    seed = jnp.asarray(seed, jnp.int32).reshape(-1)[:1]
    return jnp.stack([seed[0], jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)]).reshape(3, 1)


def dropout_keep_dense(seed, b, h, sq, sk, rate, q_off=0, k_off=0):
    """Dense (b, h, sq, sk) keep mask — the SAME bits as the in-kernel
    hash (i = flattened batch*head index), for the jnp blockwise paths
    and parity tests."""
    seed = jnp.asarray(seed, jnp.int32).reshape(-1)[:1][0]
    i = jnp.arange(b * h, dtype=jnp.int32).reshape(b, h, 1, 1)
    qcol = (jnp.asarray(q_off, jnp.int32)
            + jnp.arange(sq, dtype=jnp.int32)).reshape(1, 1, sq, 1)
    krow = (jnp.asarray(k_off, jnp.int32)
            + jnp.arange(sk, dtype=jnp.int32)).reshape(1, 1, 1, sk)
    v = (seed * jnp.int32(1000003) + i
         + krow * jnp.int32(-1640531535)
         + qcol * jnp.int32(-2048144777))
    v = _fmix32(v)
    r = v & jnp.int32(0x7FFFFFFF)
    thresh = jnp.int32(int(rate * 2147483648.0))
    return r >= thresh


# --------------------------- reference (jnp) path ---------------------------

def attention_reference(q, k, v, *, causal=False, softmax_scale=None,
                        bias=None, q_segment_ids=None, kv_segment_ids=None,
                        dropout_rate=0.0, dropout_key=None):
    """Plain softmax attention, fp32 accumulation (the parity oracle,
    ≡ the python fallback paths in apex/contrib/multihead_attn).
    Dropout masks the post-softmax attention weights (bernoulli stream —
    a different stream than the kernel's philox, same distribution)."""
    d = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if q_segment_ids is not None:
        seg = (q_segment_ids[:, None, :, None]
               != kv_segment_ids[:, None, None, :])  # (b, 1, sq, sk)
        s = jnp.where(seg, _NEG_INF, s)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.triu(jnp.ones((sq, sk), bool), k=1)
        s = jnp.where(mask, _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    p = _dense_dropout(dropout_key, dropout_rate, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


# ------------------------------ forward kernel ------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref,
                seed_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, nk,
                dropout_rate, bias_kind, has_seg):
    """Scores run TRANSPOSED (bk, bq): the softmax statistics (m, l,
    lse) are then (1, bq) lane-major rows — fully-packed vregs instead
    of 1/128-occupied columns, and the lse/delta HBM arrays are
    (bh, nq, bq) with no minor-dim-1 tile padding (a (bh, sq, 1) fp32
    array tiles to 128x its logical size on TPU)."""
    i = pl.program_id(0)
    j = pl.program_id(1)  # q block
    t = pl.program_id(2)  # k block

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _step(masked):
        # native-dtype operands: MXU wants bf16 x bf16 -> fp32; a
        # pre-upcast to fp32 would push the matmul off the MXU
        st = jax.lax.dot_general(k_ref[0], q_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        st = _mask_bias(st, j, t, bq, bk, masked, bias_kind, bias_ref,
                        has_seg, qseg_ref, kseg_ref)
        m_prev = m_scr[...]                                     # (1, bq)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        p = jnp.exp(st - m_new)                                 # (bk, bq)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        if dropout_rate > 0.0:
            # dropout is linear in p, so masking before the (deferred)
            # 1/l normalization equals dropout(softmax(s)) exactly; the
            # denominator l stays the raw softmax sum
            keep = _dropout_keep(seed_ref, i, j, t, (bk, bq), dropout_rate)
            p_acc = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
        else:
            p_acc = p
        # acc is kept transposed (d, bq) so alpha/l rows broadcast along
        # lanes; (bk, d)^T-contract (bk, bq) -> (d, bq)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            v_ref[0], p_acc.astype(v_ref.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _causal_dispatch(_step, j, t, bq, bk, causal)

    @pl.when(t == nk - 1)
    def _epilogue():
        l = jnp.maximum(l_scr[...], 1e-30)                      # (1, bq)
        o_ref[0] = (acc_scr[...] / l).T.astype(o_ref.dtype)
        # lse rides as (1, nq, bq) per-head block; write q-block row j
        lse_ref[0, j] = (m_scr[...] + jnp.log(l)).reshape(bq)


# ----------------------- head-packed forward kernel -------------------------
#
# d=64 heads half-fill the 128-deep MXU contraction port, and the
# per-step softmax/rescale epilogue runs on (1, bq) stat rows that
# occupy one sublane of an 8-sublane fp32 vreg.  Packing hp heads per
# grid step (grid axis 0 over head GROUPS) attacks both overheads: the
# K/V/Q DMAs move hp-head slabs, the grid runs 1/hp the steps, and the
# online-softmax statistics become (hp, bq) blocks whose max/exp/
# rescale chains fill the vregs across heads — one shared epilogue for
# the whole group.  The per-head matmuls stay separate (a d=64
# contraction is a hardware fact no packing changes — docs/PERF.md
# roofline scores against the shape-achievable mix), executed as a
# static unrolled loop so numerics are bit-identical to the unpacked
# kernel per head.


def _mask_bias_packed(st, j, t, bq, bk, hp, causal_masked, bias_kind,
                      bias_ref, bias_per_head, has_seg, qseg_ref,
                      kseg_ref):
    """_mask_bias over an (hp, bk, bq) stacked score block.  Bias blocks
    are (1, hp, bk, bq) when per-head (nh > 1) else (1, 1, bk, bq)
    broadcast; segment ids and the causal mask depend only on (j, t) so
    one (bk, bq) mask broadcasts across the packed heads."""
    if bias_kind == "full":
        st = st + bias_ref[0]                       # (hp|1, bk, bq)
    elif bias_kind == "sk":
        nh_blk = hp if bias_per_head else 1
        st = st + bias_ref[0, :, 0].reshape(nh_blk, bk, 1)
    if has_seg:
        qs = qseg_ref[0, j]                         # (bq,) lanes
        ks = kseg_ref[0, t].reshape(1, bk, 1)
        st = jnp.where(ks != qs, _NEG_INF, st)
    if causal_masked:
        krow = t * bk + lax.broadcasted_iota(jnp.int32, (1, bk, bq), 1)
        qcol = j * bq + lax.broadcasted_iota(jnp.int32, (1, bk, bq), 2)
        st = jnp.where(krow > qcol, _NEG_INF, st)
    return st


def _fwd_kernel_packed(q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref,
                       seed_ref, o_ref, lse_ref,
                       m_scr, l_scr, acc_scr, *, scale, causal, bq, bk,
                       nk, hp, dropout_rate, bias_kind, bias_per_head,
                       has_seg):
    """_fwd_kernel over hp packed heads: scores stack to (hp, bk, bq),
    stats/lse are (hp, bq) lane-major blocks, the accumulator is
    (hp, d, bq).  Per-head math is identical to the unpacked kernel —
    the packing only batches it."""
    i = pl.program_id(0)  # batch * head-group
    j = pl.program_id(1)  # q block
    t = pl.program_id(2)  # k block

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _step(masked):
        st = jnp.stack([
            jax.lax.dot_general(k_ref[p], q_ref[p],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for p in range(hp)]) * scale            # (hp, bk, bq)
        st = _mask_bias_packed(st, j, t, bq, bk, hp, masked, bias_kind,
                               bias_ref, bias_per_head, has_seg,
                               qseg_ref, kseg_ref)
        m_prev = m_scr[...]                         # (hp, bq)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=1))
        p_exp = jnp.exp(st - m_new[:, None, :])     # (hp, bk, bq)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p_exp, axis=1)
        if dropout_rate > 0.0:
            # per-head coordinate hash with the FLAT batch*head index
            # i*hp + p — bit-identical to the unpacked kernel's mask
            keep = jnp.stack([
                _dropout_keep(seed_ref, i * hp + p, j, t, (bk, bq),
                              dropout_rate) for p in range(hp)])
            p_acc = jnp.where(keep, p_exp, 0.0) * (
                1.0 / (1.0 - dropout_rate))
        else:
            p_acc = p_exp
        acc_scr[...] = acc_scr[...] * alpha[:, None, :] + jnp.stack([
            jax.lax.dot_general(v_ref[p], p_acc[p].astype(v_ref.dtype),
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for p in range(hp)])                    # (hp, d, bq)
        m_scr[...] = m_new

    _causal_dispatch(_step, j, t, bq, bk, causal)

    @pl.when(t == nk - 1)
    def _epilogue():
        l = jnp.maximum(l_scr[...], 1e-30)          # (hp, bq)
        o_ref[...] = jnp.swapaxes(acc_scr[...] / l[:, None, :],
                                  1, 2).astype(o_ref.dtype)
        lse_ref[:, j] = m_scr[...] + jnp.log(l)


# ------------------------------ backward kernels ----------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   bias_ref, qseg_ref, kseg_ref,
                   seed_ref, dq_ref, *rest, scale, causal, bq, bk, nk,
                   dropout_rate, bias_kind, has_seg, want_dbias=False):
    if want_dbias:          # "full"-bias grad: ds IS the dbias block
        db_ref, dq_scr = rest
    else:
        db_ref, (dq_scr,) = None, rest
    i = pl.program_id(0)
    j = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if want_dbias:
        # causal-skipped blocks never run _step: zero first, overwrite
        # in-step (same VMEM-resident block, ordered within this step)
        db_ref[0] = jnp.zeros_like(db_ref[0])

    def _step(masked):
        # transposed scores (bk, bq): lse/delta are (1, bq) lane rows
        st = jax.lax.dot_general(k_ref[0], q_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        st = _mask_bias(st, j, t, bq, bk, masked, bias_kind, bias_ref,
                        has_seg, qseg_ref, kseg_ref)
        p = jnp.exp(st - lse_ref[0, j])                         # (bk, bq)
        dp = jax.lax.dot_general(v_ref[0], do_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, i, j, t, (bk, bq), dropout_rate)
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout_rate))
        ds = p * (dp - delta_ref[0, j])                         # (bk, bq)
        if want_dbias:
            db_ref[0] = ds
        # (bk, bq)^T-contract (bk, d) -> (bq, d)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(_step, j, t, bq, bk, causal)

    @pl.when(t == nk - 1)
    def _epilogue():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    bias_ref, qseg_ref, kseg_ref,
                    seed_ref, dk_ref, dv_ref, *rest, scale,
                    causal, bq, bk, nq, dropout_rate, bias_kind, has_seg,
                    want_dbias=False):
    if want_dbias:          # "sk"-bias grad: q-summed ds rows
        db_ref, dk_scr, dv_scr, dbr_scr = rest
    else:
        db_ref = dbr_scr = None
        dk_scr, dv_scr = rest
    i = pl.program_id(0)
    t = pl.program_id(1)  # k block
    j = pl.program_id(2)  # q block (sequential inner)

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if want_dbias:
            dbr_scr[...] = jnp.zeros_like(dbr_scr)

    def _step(masked):
        # transposed scores (bk, bq): lse/delta are (1, bq) lane rows
        st = jax.lax.dot_general(k_ref[0], q_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        st = _mask_bias(st, j, t, bq, bk, masked, bias_kind, bias_ref,
                        has_seg, qseg_ref, kseg_ref)
        p = jnp.exp(st - lse_ref[0, j])                 # (bk, bq)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, i, j, t, (bk, bq), dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_v = jnp.where(keep, p, 0.0) * inv
        else:
            p_v = p
        dv_scr[...] += jax.lax.dot_general(
            p_v.astype(do_ref.dtype), do_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bk, d)
        dp = jax.lax.dot_general(v_ref[0], do_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = jnp.where(keep, dp, 0.0) * inv
        ds = p * (dp - delta_ref[0, j])                 # (bk, bq)
        if want_dbias:
            # q-sum of ds as a LANE-major (1, bk) row via the MXU
            # (ones-contract) — no sublane→lane relayout
            dbr_scr[...] += jax.lax.dot_general(
                jnp.ones((1, bq), jnp.float32), ds,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # (1, bk)
        dk_scr[...] += scale * jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bk, d)

    _causal_dispatch(_step, j, t, bq, bk, causal)

    @pl.when(j == nq - 1)
    def _epilogue():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        if want_dbias:
            # db rides as (1, nk, bk) whole-head rows (≡ the lse layout
            # trick): write k-block row t
            db_ref[0, t] = dbr_scr[...].reshape(bk)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      bias_ref, qseg_ref, kseg_ref,
                      seed_ref, dq_ref, dk_ref, dv_ref, *rest,
                      scale, causal, bq, bk,
                      nq, nk, dropout_rate, bias_kind, has_seg,
                      want_dbias=False):
    """Single-pass backward: dq, dk, dv from ONE score/exp recompute.

    The two-kernel split recomputes st/p twice (7 matmuls + 2 exp
    chains); this fused grid (bh, q-block, k-block) does 5 matmuls + 1
    exp chain.  dq accumulates per q block over the inner k loop (the
    usual pattern); dk/dv accumulate across the OUTER q loop in a
    full-(sk, d) VMEM scratch, which caps this path at moderate sk —
    _bwd_impl falls back to the two-kernel path beyond that."""
    if want_dbias:          # "full"-bias grad: ds IS the dbias block
        db_ref, dq_scr, dk_scr, dv_scr = rest
    else:
        db_ref = None
        dq_scr, dk_scr, dv_scr = rest
    i = pl.program_id(0)
    j = pl.program_id(1)  # q block (outer)
    t = pl.program_id(2)  # k block (inner)

    @pl.when(t == 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if want_dbias:
        db_ref[0] = jnp.zeros_like(db_ref[0])

    @pl.when((j == 0) & (t == 0))
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _step(masked):
        rows = (pl.ds(t * bk, bk), slice(None))
        st = jax.lax.dot_general(k_ref[0], q_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        st = _mask_bias(st, j, t, bq, bk, masked, bias_kind, bias_ref,
                        has_seg, qseg_ref, kseg_ref)
        p = jnp.exp(st - lse_ref[0, j])                 # (bk, bq)
        dp = jax.lax.dot_general(v_ref[0], do_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, i, j, t, (bk, bq), dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_v = jnp.where(keep, p, 0.0) * inv
            dp = jnp.where(keep, dp, 0.0) * inv
        else:
            p_v = p
        dv_scr[rows] += jax.lax.dot_general(
            p_v.astype(do_ref.dtype), do_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bk, d)
        ds = p * (dp - delta_ref[0, j])                 # (bk, bq)
        if want_dbias:
            db_ref[0] = ds
        dk_scr[rows] += scale * jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bk, d)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, d)

    _causal_dispatch(_step, j, t, bq, bk, causal)

    @pl.when(t == nk - 1)
    def _write_dq():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

    # dk/dv blocks are flushed to HBM every t step (their block index
    # advances with t); only the final q pass (j == nq-1) leaves the
    # complete sums behind — earlier writes are overwritten
    dk_ref[0] = dk_scr[pl.ds(t * bk, bk), :].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[pl.ds(t * bk, bk), :].astype(dv_ref.dtype)


def _bwd_fused_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref,
                             delta_ref, bias_ref, qseg_ref, kseg_ref,
                             seed_ref, dq_ref, dk_ref, dv_ref,
                             dq_scr, dk_scr, dv_scr, *, scale, causal,
                             bq, bk, nq, nk, hp, dropout_rate,
                             bias_kind, bias_per_head, has_seg):
    """_bwd_fused_kernel over hp packed heads (no dbias — _bwd_impl
    drops to the unpacked kernels when a bias gradient is wanted).
    dq accumulates per (group, q block); dk/dv accumulate across the
    outer q loop in (hp, sk, d) VMEM scratch — the packed VMEM cap is
    checked host-side (_FUSED_BWD_CAP_PACKED)."""
    i = pl.program_id(0)  # batch * head-group
    j = pl.program_id(1)  # q block (outer)
    t = pl.program_id(2)  # k block (inner)

    @pl.when(t == 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when((j == 0) & (t == 0))
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _step(masked):
        rows = (slice(None), pl.ds(t * bk, bk), slice(None))
        st = jnp.stack([
            jax.lax.dot_general(k_ref[p], q_ref[p],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for p in range(hp)]) * scale            # (hp, bk, bq)
        st = _mask_bias_packed(st, j, t, bq, bk, hp, masked, bias_kind,
                               bias_ref, bias_per_head, has_seg,
                               qseg_ref, kseg_ref)
        p_exp = jnp.exp(st - lse_ref[:, j][:, None, :])
        dp = jnp.stack([
            jax.lax.dot_general(v_ref[p], do_ref[p],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for p in range(hp)])                    # (hp, bk, bq)
        if dropout_rate > 0.0:
            keep = jnp.stack([
                _dropout_keep(seed_ref, i * hp + p, j, t, (bk, bq),
                              dropout_rate) for p in range(hp)])
            inv = 1.0 / (1.0 - dropout_rate)
            p_v = jnp.where(keep, p_exp, 0.0) * inv
            dp = jnp.where(keep, dp, 0.0) * inv
        else:
            p_v = p_exp
        dv_scr[rows] += jnp.stack([
            jax.lax.dot_general(p_v[p].astype(do_ref.dtype), do_ref[p],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for p in range(hp)])                    # (hp, bk, d)
        ds = p_exp * (dp - delta_ref[:, j][:, None, :])
        dk_scr[rows] += scale * jnp.stack([
            jax.lax.dot_general(ds[p].astype(q_ref.dtype), q_ref[p],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for p in range(hp)])                    # (hp, bk, d)
        dq_scr[...] += scale * jnp.stack([
            jax.lax.dot_general(ds[p].astype(k_ref.dtype), k_ref[p],
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for p in range(hp)])                    # (hp, bq, d)

    _causal_dispatch(_step, j, t, bq, bk, causal)

    @pl.when(t == nk - 1)
    def _write_dq():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)

    # dk/dv flushed every t step (block index advances with t); only
    # the final q pass leaves the complete sums (≡ _bwd_fused_kernel)
    dk_ref[...] = dk_scr[:, pl.ds(t * bk, bk), :].astype(dk_ref.dtype)
    dv_ref[...] = dv_scr[:, pl.ds(t * bk, bk), :].astype(dv_ref.dtype)


# ----------------------------- host-side plumbing ---------------------------

def _pick_block(seq, cap=512):
    for b in (1024, 512, 256, 128, 64, 32, 16, 8):
        if b <= cap and seq % b == 0:
            return b
    return None


_BLOCK_FALLBACK_WARNED = set()


def _fit_block(blk, seq, name):
    """Largest power-of-two block <= blk that divides seq.  Tuned
    configs are swept at the bench shapes; an off-size sequence (odd
    microbatch remainder, a probe script) must degrade to a dividing
    block instead of hard-failing mid-training (warn once per
    (name, blk, seq))."""
    if blk is None or seq % blk == 0:
        return blk
    fb = _pick_block(seq, cap=blk)
    if fb is None:
        raise ValueError(
            f"{name}={blk} does not divide seq={seq} and no smaller "
            f"power-of-two block divides it either")
    key = (name, blk, seq)
    if key not in _BLOCK_FALLBACK_WARNED:
        _BLOCK_FALLBACK_WARNED.add(key)
        warnings.warn(
            f"flash attention: {name}={blk} does not divide seq={seq}; "
            f"falling back to the largest dividing block {fb}",
            stacklevel=4)
    return fb


def _resolve_blocks(sq, sk, block_q, block_k, full_bias=False):
    """Default blocks, swept on v5e (docs/PERF.md): single block per
    axis when the sequence fits (<=1024 — grid overhead dominates the
    extra causal-mask work), else (512, 1024) to cap the fp32 score
    tile at 2 MB of VMEM while keeping k-side matmuls wide.  Explicit
    blocks that do not divide the sequence fall back to the largest
    dividing power-of-two block (warn once) so tuned configs never
    hard-fail on off-size sequences.  A fused FULL bias adds a
    same-size fp32 block, so the q block is halved to stay inside VMEM
    (a key-compact "sk" bias is only a (bk,) row — no halving)."""
    block_q = _fit_block(block_q, sq, "block_q")
    block_k = _fit_block(block_k, sk, "block_k")
    q_cap = 1024 if (sq <= 1024 and not full_bias) else 512
    bq = block_q or _pick_block(sq, cap=q_cap)
    bk = block_k or _pick_block(sk, cap=1024)
    return bq, bk


def _resolve_heads_per_step(heads_per_step, h, want_dbias=False):
    """Validated packing factor: must divide the (local) head count;
    dbias paths run unpacked.  Invalid explicit values warn once and
    fall back to 1 (the tuned path must degrade, not fail)."""
    hp = int(heads_per_step or 1)
    if hp <= 1:
        return 1
    if want_dbias:
        return 1
    if h % hp:
        key = ("heads_per_step", hp, h)
        if key not in _BLOCK_FALLBACK_WARNED:
            _BLOCK_FALLBACK_WARNED.add(key)
            warnings.warn(
                f"flash attention: heads_per_step={hp} does not divide "
                f"num_heads={h}; running unpacked", stacklevel=4)
        return 1
    return hp


def _compiler_params(grid_len):
    # first axes (batch*head and the parallel block axis) are
    # order-independent; the innermost axis carries the online-softmax /
    # accumulator recurrence and must stay sequential
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_len - 1) + ("arbitrary",))


def _flatten_bh(x):
    b, h, s, d = x.shape
    return x.reshape(b * h, s, d)


def _fwd_impl(q, k, v, scale, causal, dropout_rate=0.0, seed=None,
              block_q=None, block_k=None, bias=None, q_seg=None,
              kv_seg=None, q_off=0, k_off=0, heads_per_step=1):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bias_kind = _bias_kind(bias, sk)
    bq, bk = _resolve_blocks(sq, sk, block_q, block_k,
                              full_bias=bias_kind == "full")
    hp = _resolve_heads_per_step(heads_per_step, h)
    qf, kf, vf = _flatten_bh(q), _flatten_bh(k), _flatten_bh(v)
    bh = b * h
    nq, nk = sq // bq, sk // bk
    seed = _seed3(seed, q_off, k_off)
    has_seg = q_seg is not None
    nb = bias.shape[0] if bias is not None else 1
    nh = bias.shape[1] if bias is not None else 1
    bias_t, qs, ks = _extras_arrays(b, h, sq, sk, nq, bq, nk, bk,
                                    bias, q_seg, kv_seg, bias_kind)
    bspec, qsspec, ksspec = _extras_specs(
        h, nq, bq, nk, bk, bias_kind, nb, nh, has_seg,
        jt_from_args=lambda j, t: (j, t), hp=hp)
    if hp == 1:
        kernel = functools.partial(
            _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
            nk=nk, dropout_rate=dropout_rate, bias_kind=bias_kind,
            has_seg=has_seg)
        scratch = [pltpu.VMEM((1, bq), jnp.float32),
                   pltpu.VMEM((1, bq), jnp.float32),
                   pltpu.VMEM((d, bq), jnp.float32)]
    else:
        kernel = functools.partial(
            _fwd_kernel_packed, scale=scale, causal=causal, bq=bq,
            bk=bk, nk=nk, hp=hp, dropout_rate=dropout_rate,
            bias_kind=bias_kind, bias_per_head=nh > 1, has_seg=has_seg)
        scratch = [pltpu.VMEM((hp, bq), jnp.float32),
                   pltpu.VMEM((hp, bq), jnp.float32),
                   pltpu.VMEM((hp, d, bq), jnp.float32)]
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh // hp, nq, nk),
        in_specs=[
            pl.BlockSpec((hp, bq, d), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((hp, bk, d), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((hp, bk, d), lambda i, j, t: (i, t, 0)),
            bspec, qsspec, ksspec,
            pl.BlockSpec((3, 1), lambda i, j, t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((hp, bq, d), lambda i, j, t: (i, j, 0)),
            # lse as (bh, nq, bq): one whole-head(-group) block resident
            # per i (a (bh, sq, 1) fp32 array would tile-pad to 128x its
            # size; 2-D (1, bq) blocks violate the (8, 128) tile rule)
            pl.BlockSpec((hp, nq, bq), lambda i, j, t: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, nq, bq), jnp.float32),
        ],
        scratch_shapes=scratch,
        # the q-block axis must stay sequential here: the whole-head lse
        # block is shared across j, and a Megacore split of a "parallel"
        # j would give each core a private copy with half the rows
        # written (last flush wins)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name="flash_fwd",
    )(qf, kf, vf, bias_t, qs, ks, seed)
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _head_row_spec(nq, bq):
    """Whole-head (1, nq, bq) block for the lse/delta row stats —
    resident across the block loops (index depends only on i, whatever
    the grid order)."""
    return pl.BlockSpec((1, nq, bq), lambda i, *_: (i, 0, 0))


def _bwd_impl(q, k, v, o, lse, do, scale, causal, dropout_rate=0.0,
              seed=None, block_q=None, block_k=None, bias=None,
              q_seg=None, kv_seg=None, want_dbias=False,
              grad_dtype=None, q_off=0, k_off=0, heads_per_step=1):
    """Returns (dq, dk, dv, dbias) — dbias is None unless want_dbias.

    grad_dtype overrides the dq/dk/dv output dtype (default: the input
    dtypes).  The ring-attention backward passes fp32 so per-ring-step
    partials accumulate at full precision instead of being rounded to
    bf16 once per ring hop (the kernels accumulate in fp32 scratch
    either way; this only moves the final rounding)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bias_kind = _bias_kind(bias, sk)
    bq, bk = _resolve_blocks(sq, sk, block_q, block_k,
                              full_bias=bias_kind == "full")
    hp = _resolve_heads_per_step(heads_per_step, h,
                                 want_dbias=want_dbias)
    nq, nk = sq // bq, sk // bk
    bh = b * h
    seed = _seed3(seed, q_off, k_off)
    has_seg = q_seg is not None
    nb = bias.shape[0] if bias is not None else 1
    nh = bias.shape[1] if bias is not None else 1
    bias_t, qsegs, ksegs = _extras_arrays(b, h, sq, sk, nq, bq, nk, bk,
                                          bias, q_seg, kv_seg, bias_kind)
    bspec, qsspec, ksspec = _extras_specs(
        h, nq, bq, nk, bk, bias_kind, nb, nh, has_seg,
        jt_from_args=lambda j, t: (j, t))
    static = dict(scale=scale, causal=causal, bq=bq, bk=bk,
                  dropout_rate=dropout_rate, bias_kind=bias_kind,
                  has_seg=has_seg)
    dq_dt = grad_dtype or q.dtype
    dk_dt = grad_dtype or k.dtype
    dv_dt = grad_dtype or v.dtype
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # (b,h,sq)
    args = [_flatten_bh(q), _flatten_bh(k), _flatten_bh(v),
            _flatten_bh(do), lse.reshape(bh, nq, bq),
            delta.reshape(bh, nq, bq), bias_t, qsegs, ksegs, seed]
    qspec = pl.BlockSpec((1, bq, d), lambda i, j, t: (i, j, 0))
    kspec = pl.BlockSpec((1, bk, d), lambda i, j, t: (i, t, 0))
    r1 = _head_row_spec(nq, bq)
    sspec1 = pl.BlockSpec((3, 1), lambda i, j, t: (0, 0))

    def _reduce_db(db_full):
        """(b, h, ...) per-head dbias partials → the caller's broadcast
        shape (nb, nh, ...)."""
        if nb == 1:
            db_full = jnp.sum(db_full, axis=0, keepdims=True)
        if nh == 1:
            db_full = jnp.sum(db_full, axis=1, keepdims=True)
        return db_full

    # dbias("full") comes from the fused/dq kernels (ds written per
    # (j, t) block); dbias("sk") needs the dkv grid (q-sum accumulates
    # over the inner j axis), so it forces the two-kernel path
    dbias_full = want_dbias and bias_kind == "full"
    dbias_sk = want_dbias and bias_kind == "sk"

    # head-packed single-pass backward: only when the fused path is
    # live anyway, no bias gradient is wanted (dbias writes are
    # per-head), and the (hp, sk, d) dk/dv scratch pair fits VMEM
    if (hp > 1 and sk * d <= _FUSED_BWD_CAP and not want_dbias
            and hp * sk * d <= _FUSED_BWD_CAP_PACKED):
        bspec_p, qsspec_p, ksspec_p = _extras_specs(
            h, nq, bq, nk, bk, bias_kind, nb, nh, has_seg,
            jt_from_args=lambda j, t: (j, t), hp=hp)
        qspec_p = pl.BlockSpec((hp, bq, d), lambda i, j, t: (i, j, 0))
        kspec_p = pl.BlockSpec((hp, bk, d), lambda i, j, t: (i, t, 0))
        rp = pl.BlockSpec((hp, nq, bq), lambda i, j, t: (i, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel_packed, nq=nq, nk=nk,
                              hp=hp, bias_per_head=nh > 1, **static),
            grid=(bh // hp, nq, nk),
            in_specs=[qspec_p, kspec_p, kspec_p, qspec_p, rp, rp,
                      bspec_p, qsspec_p, ksspec_p,
                      pl.BlockSpec((3, 1), lambda i, j, t: (0, 0))],
            out_specs=[qspec_p, kspec_p, kspec_p],
            out_shape=[jax.ShapeDtypeStruct((bh, sq, d), dq_dt),
                       jax.ShapeDtypeStruct((bh, sk, d), dk_dt),
                       jax.ShapeDtypeStruct((bh, sk, d), dv_dt)],
            scratch_shapes=[pltpu.VMEM((hp, bq, d), jnp.float32),
                            pltpu.VMEM((hp, sk, d), jnp.float32),
                            pltpu.VMEM((hp, sk, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary",
                                     "arbitrary")),
            interpret=pallas_interpret(),
            name="flash_bwd",
        )(*args)
        return (dq.reshape(q.shape), dk.reshape(k.shape),
                dv.reshape(v.shape), None)

    # single-pass fused backward while the full-(sk, d) dk/dv scratch
    # fits VMEM comfortably; two-kernel fallback for long context
    if sk * d <= _FUSED_BWD_CAP and not dbias_sk:
        out_specs = [qspec, kspec, kspec]
        out_shape = [jax.ShapeDtypeStruct((bh, sq, d), dq_dt),
                     jax.ShapeDtypeStruct((bh, sk, d), dk_dt),
                     jax.ShapeDtypeStruct((bh, sk, d), dv_dt)]
        if dbias_full:
            out_specs.append(pl.BlockSpec((1, bk, bq),
                                          lambda i, j, t: (i, t, j)))
            out_shape.append(
                jax.ShapeDtypeStruct((bh, sk, sq), jnp.float32))
        outs = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, nq=nq, nk=nk,
                              want_dbias=dbias_full, **static),
            grid=(bh, nq, nk),
            in_specs=[qspec, kspec, kspec, qspec, r1, r1,
                      bspec, qsspec, ksspec, sspec1],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((sk, d), jnp.float32),
                            pltpu.VMEM((sk, d), jnp.float32)],
            # dk/dv accumulate across the q-block axis too, so only the
            # leading batch*head axis is order-independent here
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=pallas_interpret(),
            name="flash_bwd",
        )(*args)
        dq, dk, dv = outs[:3]
        dbias = None
        if dbias_full:
            db = _reduce_db(outs[3].reshape(b, h, sk, sq))
            dbias = jnp.swapaxes(db, 2, 3)
        return (dq.reshape(q.shape), dk.reshape(k.shape),
                dv.reshape(v.shape), dbias)

    dq_specs = [qspec]
    dq_shape = [jax.ShapeDtypeStruct((bh, sq, d), dq_dt)]
    if dbias_full:
        dq_specs.append(pl.BlockSpec((1, bk, bq),
                                     lambda i, j, t: (i, t, j)))
        dq_shape.append(jax.ShapeDtypeStruct((bh, sk, sq), jnp.float32))
    dq_out = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, want_dbias=dbias_full,
                          **static),
        grid=(bh, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, r1, r1,
                  bspec, qsspec, ksspec, sspec1],
        out_specs=dq_specs if dbias_full else dq_specs[0],
        out_shape=dq_shape if dbias_full else dq_shape[0],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(3),
        interpret=pallas_interpret(),
        name="flash_bwd_dq",
    )(*args)
    dbias = None
    if dbias_full:
        dq, db_t = dq_out
        dbias = jnp.swapaxes(_reduce_db(db_t.reshape(b, h, sk, sq)), 2, 3)
    else:
        dq = dq_out
    # dkv grid: k blocks outer, q blocks inner-sequential
    qspec2 = pl.BlockSpec((1, bq, d), lambda i, t, j: (i, j, 0))
    kspec2 = pl.BlockSpec((1, bk, d), lambda i, t, j: (i, t, 0))
    r2 = _head_row_spec(nq, bq)
    sspec2 = pl.BlockSpec((3, 1), lambda i, t, j: (0, 0))
    bspec2, qsspec2, ksspec2 = _extras_specs(
        h, nq, bq, nk, bk, bias_kind, nb, nh, has_seg,
        jt_from_args=lambda t, j: (j, t))
    dkv_specs = [kspec2, kspec2]
    dkv_shape = [jax.ShapeDtypeStruct((bh, sk, d), dk_dt),
                 jax.ShapeDtypeStruct((bh, sk, d), dv_dt)]
    dkv_scratch = [pltpu.VMEM((bk, d), jnp.float32),
                   pltpu.VMEM((bk, d), jnp.float32)]
    if dbias_sk:
        # db rides as (bh, nk, bk) whole-head rows (the lse layout);
        # shared across both block axes → t must not Megacore-split
        dkv_specs.append(pl.BlockSpec((1, nk, bk),
                                      lambda i, t, j: (i, 0, 0)))
        dkv_shape.append(jax.ShapeDtypeStruct((bh, nk, bk), jnp.float32))
        dkv_scratch.append(pltpu.VMEM((1, bk), jnp.float32))
        dkv_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"))
    else:
        dkv_params = _compiler_params(3)
    outs = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, want_dbias=dbias_sk,
                          **static),
        grid=(bh, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, r2, r2,
                  bspec2, qsspec2, ksspec2, sspec2],
        out_specs=dkv_specs,
        out_shape=dkv_shape,
        scratch_shapes=dkv_scratch,
        compiler_params=dkv_params,
        interpret=pallas_interpret(),
        name="flash_bwd_dkv",
    )(*args)
    dk, dv = outs[:2]
    if dbias_sk:
        db = _reduce_db(outs[2].reshape(b, h, sk))       # (nb, nh, sk)
        dbias = db[:, :, None, :]                        # (nb, nh, 1, sk)
    return (dq.reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape), dbias)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, bias, q_seg, kv_seg, scale, causal, dropout_rate,
           block_q, block_k, heads_per_step, bias_grad, seed):
    o, _ = _fwd_impl(q, k, v, scale, causal, dropout_rate, seed,
                     block_q, block_k, bias, q_seg, kv_seg,
                     heads_per_step=heads_per_step)
    return o


def _flash_fwd(q, k, v, bias, q_seg, kv_seg, scale, causal, dropout_rate,
               block_q, block_k, heads_per_step, bias_grad, seed):
    o, lse = _fwd_impl(q, k, v, scale, causal, dropout_rate, seed,
                       block_q, block_k, bias, q_seg, kv_seg,
                       heads_per_step=heads_per_step)
    return o, (q, k, v, bias, q_seg, kv_seg, o, lse, seed)


def _flash_bwd(scale, causal, dropout_rate, block_q, block_k,
               heads_per_step, bias_grad, res, do):
    q, k, v, bias, q_seg, kv_seg, o, lse, seed = res
    # a key-broadcast (.., *, 1) bias adds a per-query constant to the
    # scores — softmax cancels it, so its gradient is EXACTLY zero (no
    # kernel work); bias_grad=False opts constant biases (padding
    # masks, fixed ALiBi) out of the dbias computation entirely
    want_dbias = (bias_grad and bias is not None and bias.shape[3] != 1)
    dq, dk, dv, dbias = _bwd_impl(q, k, v, o, lse, do, scale, causal,
                                  dropout_rate, seed, block_q, block_k,
                                  bias, q_seg, kv_seg,
                                  want_dbias=want_dbias,
                                  heads_per_step=heads_per_step)
    import numpy as _np

    def _int_zero(x):
        return (None if x is None
                else _np.zeros(x.shape, dtype=jax.dtypes.float0))
    if bias is not None:
        dbias = (dbias.astype(bias.dtype) if want_dbias
                 else jnp.zeros_like(bias))
    return (dq, dk, dv, dbias, _int_zero(q_seg), _int_zero(kv_seg),
            _int_zero(seed))


_flash.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------- public API -------------------------------

# cache-sourced score-tile guard: hp·bq·bk fp32 elements must stay
# within ~4 MB of VMEM (the sweep's own candidate cap is half this)
_TUNED_SCORE_ELEMS_CAP = 1024 * 1024


def _tuned_flash_config(b, h, sq, sk, d, dtype, causal, bias_kind,
                        has_seg):
    """Trace-time autotuner lookup (apex_tpu.tune): a pure host-side
    dict access — zero collectives, no host syncs.  None on a miss, so
    an empty cache leaves every call on today's heuristics.

    A hit is SANITY-VALIDATED before use (a hand-edited or
    cross-version cache must degrade to heuristics, never crash a run):
    blocks and packing must be ints in range and the packed fp32 score
    tile must fit VMEM; anything off warns once and is ignored
    (divisibility fixups happen later in _resolve_blocks /
    _resolve_heads_per_step)."""
    from apex_tpu import tune

    if sq != sk:
        return None   # tuned entries are swept at self-attention shapes
    cfg = tune.tuned("flash_sdpa",
                     tune.flash_attrs(b, h, sq, sk, d, dtype, causal,
                                      bias=bias_kind, seg=has_seg))
    if not cfg:
        return None
    bq = cfg.get("block_q")
    bk = cfg.get("block_k")
    hp = cfg.get("heads_per_step", 1)
    ok = (all(v is None or (isinstance(v, int) and 8 <= v <= 4096)
              for v in (bq, bk))
          and isinstance(hp, int) and 1 <= hp <= 16
          and hp * (bq or 1024) * (bk or 1024) <= _TUNED_SCORE_ELEMS_CAP)
    if not ok:
        key = ("tuned_cfg", sq, sk, d)
        if key not in _BLOCK_FALLBACK_WARNED:
            _BLOCK_FALLBACK_WARNED.add(key)
            warnings.warn(
                f"flash attention: ignoring out-of-range tuned config "
                f"{cfg} at (sq={sq}, sk={sk}, d={d}); using heuristics",
                stacklevel=3)
        return None
    return cfg


def flash_attention(q, k, v, *, causal: bool = False,
                    softmax_scale: Optional[float] = None,
                    bias=None,
                    segment_ids=None,
                    q_segment_ids=None,
                    kv_segment_ids=None,
                    dropout_rate: float = 0.0,
                    dropout_key=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    heads_per_step: Optional[int] = None,
                    # True by default DELIBERATELY: a trainable bias
                    # silently freezing (the round-3 contract) is wrong
                    # training with no error; the full-bias dbias
                    # buffer this costs is a loud, debuggable OOM whose
                    # opt-out (bias_grad=False) is documented below.
                    bias_grad: bool = True,
                    use_pallas_override: Optional[bool] = None):
    """Flash attention over (batch, heads, seq, head_dim).

    ≡ apex.contrib.fmha.FMHAFun (apex/contrib/fmha/fmha.py:33-72) with
    the seq≤512/head-64 restriction removed, and the core of the
    fast_multihead_attn variants (self/encdec attention cores).
    Attention dropout runs IN-kernel with a counter-based mask
    regenerated in backward (≡ the reference's philox dropout,
    fmha/src/fmha/softmax.h) — no sq x sk mask ever reaches HBM, so
    dropout works at any sequence length.

    bias: additive fp score bias, shape (b|1, h|1, sq|1, sk), fused
    into the kernel (≡ the additive-mask softmax in
    apex/contrib/csrc/multihead_attn/softmax.cuh:27-200).  A
    key-compact (.., 1, sk) bias — the padding-mask / ALiBi shape —
    rides compact through the kernels (never expanded to sq × sk in
    HBM).  TRAINABLE biases are first-class (≡ the
    self_multihead_attn_bias CUDA variants): the backward emits the
    real dbias, reduced over broadcast dims — full (sq, sk) biases
    from per-block ds writes, key-compact ones from an in-kernel
    q-sum.  COST NOTE: a differentiated call with a full (sq, sk)
    bias materializes a per-(b, h) fp32 dbias partial (b·h·sq·sk
    bytes ×4 transient) before the broadcast reduction — pass
    bias_grad=False for constant biases (padding masks, fixed slopes)
    to skip all dbias work, as the in-repo mask paths do.  A
    (.., *, 1) query-compact bias is a per-query score constant:
    softmax cancels it exactly (finite values; whole-row masking must
    use segment ids), so it is skipped in the kernels and its gradient
    is exactly zero.

    block_q / block_k / heads_per_step: the kernel-shape knobs.
    heads_per_step > 1 packs that many d-minor heads into each grid
    step (shared online-softmax epilogue, hp-head K/V slabs per DMA —
    the d=64 packing axis; see _fwd_kernel_packed).  When ALL THREE are
    None the apex_tpu.tune cache is consulted at trace time for a
    config tuned at this exact (shape, dtype, device-kind) key — a
    cache miss (or APEX_TPU_TUNE=0) keeps the built-in heuristics, so
    an empty cache is byte-identical to explicit None everywhere.
    Explicit blocks that do not divide the sequence fall back to the
    largest dividing block (warn once) instead of failing.

    segment_ids: (b, s) int — tokens attend only where ids are equal;
    this is the TPU-native form of the reference fmha's cu_seqlens
    varlen packing (fmha_api.cpp:18-160): pack multiple sequences into
    one row with distinct ids and padded tokens cost no attention.
    q_segment_ids/kv_segment_ids set the two sides separately (encdec
    or kv-cache shapes); fully-masked query rows produce a uniform
    attention over kv (like the dense oracle) — mask them in the loss.
    """
    d = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    if dropout_rate > 0.0 and dropout_key is None:
        raise ValueError("dropout_rate > 0 requires dropout_key")
    if segment_ids is not None:
        if q_segment_ids is not None or kv_segment_ids is not None:
            raise ValueError(
                "pass either segment_ids or q_/kv_segment_ids, not both")
        q_segment_ids = kv_segment_ids = segment_ids
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("q_segment_ids and kv_segment_ids go together")
    b, h = q.shape[0], q.shape[1]
    sq, sk = q.shape[2], k.shape[2]
    if bias is not None:
        eb, eh = bias.shape[0], bias.shape[1]
        if (bias.ndim != 4 or eb not in (1, b) or eh not in (1, h)
                or bias.shape[2] not in (1, sq)
                or bias.shape[3] not in (1, sk)):
            raise ValueError(
                f"bias shape {bias.shape} not broadcastable to "
                f"({b}|1, {h}|1, {sq}|1, {sk}|1)")
    if q_segment_ids is not None:
        q_segment_ids = jnp.asarray(q_segment_ids, jnp.int32)
        kv_segment_ids = jnp.asarray(kv_segment_ids, jnp.int32)
        if q_segment_ids.shape != (b, sq) or kv_segment_ids.shape != (b, sk):
            raise ValueError(
                f"segment id shapes {q_segment_ids.shape}/"
                f"{kv_segment_ids.shape} != ({b}, {sq})/({b}, {sk})")
    # in-kernel dropout is a pure coordinate hash — it runs (and gives
    # bit-identical masks) in interpret mode too, so CPU CI covers it
    kernel_ok = (use_pallas(use_pallas_override)
                 and _pick_block(q.shape[2]) and _pick_block(k.shape[2]))
    if kernel_ok:
        if block_q is None and block_k is None and heads_per_step is None:
            # fully-unspecified config → consult the autotuner cache
            # (explicit knobs always win; a miss keeps the heuristics)
            cfg = _tuned_flash_config(
                b, h, sq, sk, q.shape[3], q.dtype, causal,
                _bias_kind(bias, sk), q_segment_ids is not None)
            if cfg:
                block_q = cfg.get("block_q")
                block_k = cfg.get("block_k")
                heads_per_step = cfg.get("heads_per_step")
        if dropout_rate > 0.0:
            seed = jax.random.randint(dropout_key, (1, 1), -2**31, 2**31 - 1,
                                      dtype=jnp.int32)
        else:
            seed = jnp.zeros((1, 1), jnp.int32)
        return _flash(q, k, v, bias, q_segment_ids, kv_segment_ids,
                      scale, causal, float(dropout_rate),
                      block_q, block_k, int(heads_per_step or 1),
                      bool(bias_grad), seed)
    # fallback keeps the same dbias semantics: AD through the dense
    # path yields the (broadcast-reduced) dbias when bias_grad, and a
    # stop_gradient reproduces the constant-bias contract otherwise
    return attention_reference(q, k, v, causal=causal, softmax_scale=scale,
                               bias=(bias if bias is None or bias_grad
                                     else lax.stop_gradient(bias)),
                               q_segment_ids=q_segment_ids,
                               kv_segment_ids=kv_segment_ids,
                               dropout_rate=dropout_rate,
                               dropout_key=dropout_key)
