"""Context parallelism for long sequences — ring attention + Ulysses.

The reference's only long-sequence mechanism is Megatron SP + fixed-size
FMHA kernels (SURVEY §5.7: no ring attention, no Ulysses).  For the TPU
framework long context is first-class:

* `ring_attention` — sequence (and KV) sharded over a mesh axis; KV
  chunks rotate around the ICI ring with `ppermute` while each device
  merges per-chunk blockwise-attention results into its queries'
  running online-softmax state.  v2 design:

  - each ring step runs the SAME blockwise flash kernel as single-chip
    attention (`ops/flash_attention._fwd_impl`) on the resident
    (s_local × s_local) chunk pair — the (s²) score matrix never
    reaches HBM, on any backend (a jnp blockwise scan stands in for
    Pallas off-TPU);
  - a `custom_vjp` recomputes the backward from the saved (o, lse)
    instead of AD-through-scan: per-device residuals are
    q, k, v, o (s_local × d) + lse (s_local) — linear in s_local, NOT
    the O(n · s_local²) of differentiating through the forward scan;
  - causal chunks strictly above the diagonal are SKIPPED (a
    `lax.switch` branch that touches no scores), not masked: a causal
    ring costs ~half the FLOPs of the full ring;
  - segment ids rotate with their KV chunk, so packed-varlen batches
    work across the ring exactly as they do in-kernel;
  - layout="zigzag" (with `zigzag_shard`/`zigzag_unshard`) balances
    the causal load: device r owns the half-chunk pair (r, 2n-1-r),
    every device runs exactly two half-computes per step, and the
    causal ring's wall-clock HALVES vs the contiguous layout (whose
    last rank computes at every step).

  Peak per-device memory: O(s_local · d) tensors + one (block × block)
  score tile — global sequence length scales linearly with ring size.

* `ulysses_attention` — all-to-all head scatter: convert seq-sharding
  to head-sharding with `lax.all_to_all`, run (flash) attention on
  full sequences of the local heads, convert back.  One collective
  pair per attention instead of n ring hops; needs heads % axis == 0.

Both compose with the TP layers (use a separate mesh axis or reuse
"tp" when attention is not head-sharded).  In-kernel attention dropout
works on the ring path too: each chunk hashes its GLOBAL (q, k)
sequence offsets into the coordinate-hash keep mask, so all ring steps
and the backward draw from ONE global mask — bit-identical to
single-chip flash attention over the gathered sequence (tested in
tests/test_context_parallel.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.ops._common import use_pallas
from apex_tpu.ops.flash_attention import (
    _NEG_INF,
    _bwd_impl,
    _fwd_impl,
    _kernel_shape,
    _pick_block,
    dropout_keep_dense,
)


# ------------------------- per-chunk blockwise attention ---------------------

def _jnp_blocks(sk, block_k):
    if block_k is not None and sk % block_k:
        raise ValueError(f"block_k={block_k} does not divide "
                         f"s_local={sk}")
    bk = block_k or _pick_block(sk, cap=1024)
    if bk is None:
        bk = sk  # no power-of-two divisor: single block
    return bk, sk // bk


def _chunk_fwd_jnp(q, k, v, scale, causal, q_seg, kv_seg, block_k,
                   dropout_rate=0.0, seed=None, q_off=0, k_off=0):
    """Blockwise online-softmax forward in plain jnp (the off-TPU stand-in
    for the Pallas kernel): scans k-blocks so peak score memory is
    (sq × block_k), never (sq × sk).  Returns (o, lse).  Dropout uses
    the kernel's global-coordinate hash (dropout_keep_dense), masking p
    before the deferred 1/l normalization (the l denominator stays the
    raw softmax sum, ≡ _fwd_kernel)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bk, nk = _jnp_blocks(sk, block_k)
    q32 = q.astype(jnp.float32)
    qpos = jnp.arange(sq)

    def step(carry, t):
        m, l, o = carry
        k_t = lax.dynamic_slice_in_dim(k, t * bk, bk, 2).astype(jnp.float32)
        v_t = lax.dynamic_slice_in_dim(v, t * bk, bk, 2).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_t) * scale
        if q_seg is not None:
            ks_t = lax.dynamic_slice_in_dim(kv_seg, t * bk, bk, 1)
            s = jnp.where(q_seg[:, None, :, None] != ks_t[:, None, None, :],
                          _NEG_INF, s)
        if causal:
            kpos = t * bk + jnp.arange(bk)
            s = jnp.where(kpos[None, :] > qpos[:, None], _NEG_INF, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        if dropout_rate > 0.0:
            keep = dropout_keep_dense(seed, b, h, sq, bk, dropout_rate,
                                      q_off, k_off + t * bk)
            p_acc = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
        else:
            p_acc = p
        o_new = o * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd",
                                                  p_acc, v_t)
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, o), _ = lax.scan(step, (m0, l0, o0), jnp.arange(nk))
    l = jnp.maximum(l, 1e-30)
    return (o / l[..., None]).astype(q.dtype), m + jnp.log(l)


def _chunk_bwd_jnp(q, k, v, do, lse, delta, scale, causal, q_seg, kv_seg,
                   block_k, dropout_rate=0.0, seed=None, q_off=0,
                   k_off=0):
    """Blockwise backward against the GLOBAL (lse, delta) — the partials
    this produces sum across ring steps to the exact gradient.  Dropout
    regenerates the forward's coordinate-hash mask (≡ _bwd_dkv_kernel:
    dv uses dropped p, dp is masked before ds)."""
    b, h = q.shape[0], q.shape[1]
    sq = q.shape[2]
    sk = k.shape[2]
    bk, nk = _jnp_blocks(sk, block_k)
    q32 = q.astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    qpos = jnp.arange(q.shape[2])

    def step(dq, t):
        k_t = lax.dynamic_slice_in_dim(k, t * bk, bk, 2).astype(jnp.float32)
        v_t = lax.dynamic_slice_in_dim(v, t * bk, bk, 2).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_t) * scale
        if q_seg is not None:
            ks_t = lax.dynamic_slice_in_dim(kv_seg, t * bk, bk, 1)
            s = jnp.where(q_seg[:, None, :, None] != ks_t[:, None, None, :],
                          _NEG_INF, s)
        if causal:
            kpos = t * bk + jnp.arange(bk)
            s = jnp.where(kpos[None, :] > qpos[:, None], _NEG_INF, s)
        p = jnp.exp(s - lse[..., None])                    # global-normalized
        dp = jnp.einsum("bhqd,bhkd->bhqk", do32, v_t)
        if dropout_rate > 0.0:
            keep = dropout_keep_dense(seed, b, h, sq, bk, dropout_rate,
                                      q_off, k_off + t * bk)
            inv = 1.0 / (1.0 - dropout_rate)
            p_v = jnp.where(keep, p, 0.0) * inv
            dp = jnp.where(keep, dp, 0.0) * inv
        else:
            p_v = p
        ds = p * (dp - delta[..., None])
        dq = dq + scale * jnp.einsum("bhqk,bhkd->bhqd", ds, k_t)
        dk_t = scale * jnp.einsum("bhqk,bhqd->bhkd", ds, q32)
        dv_t = jnp.einsum("bhqk,bhqd->bhkd", p_v, do32)
        return dq, (dk_t, dv_t)

    dq0 = jnp.zeros(q.shape[:3] + (q.shape[3],), jnp.float32)
    dq, (dk_b, dv_b) = lax.scan(step, dq0, jnp.arange(nk))
    # stacked (nk, b, h, bk, d) → (b, h, sk, d)
    def unblock(x):
        return jnp.moveaxis(x, 0, 2).reshape(k.shape[:2] + (sk, k.shape[3]))
    return dq, unblock(dk_b), unblock(dv_b)


def _chunk_shape(q, k, v, causal, block_q, block_k):
    """The kernel shape of one chunk against one chunk: the single-chip
    kernels' own decision at the ring's blocks, without a tuner lookup
    (no sweep ran at a chunk's shape).  A function of the chunk's shape
    alone, so a ring step's backward rebuilds its forward's."""
    return _kernel_shape(q.shape[2], k.shape[2], q.shape[3], v.shape[3],
                         q.dtype, causal, block_q=block_q, block_k=block_k)


def _chunk_fwd(q, k, v, scale, causal, q_seg, kv_seg, block_q, block_k,
               pallas_path, dropout_rate=0.0, seed=None, q_off=0,
               k_off=0):
    if pallas_path:
        return _fwd_impl(q, k, v, scale, causal,
                         _chunk_shape(q, k, v, causal, block_q, block_k),
                         dropout_rate, seed, None, q_seg, kv_seg,
                         q_off=q_off, k_off=k_off)
    return _chunk_fwd_jnp(q, k, v, scale, causal, q_seg, kv_seg, block_k,
                          dropout_rate, seed, q_off, k_off)


def _chunk_bwd(q, k, v, o, lse, delta, do, scale, causal, q_seg, kv_seg,
               block_q, block_k, pallas_path, dropout_rate=0.0,
               seed=None, q_off=0, k_off=0):
    if pallas_path:
        # fp32 partials straight from the kernel: per-ring-step grads
        # accumulate across hops at full precision and round to the
        # input dtype ONCE at the end (ADVICE r4 — bf16-per-hop rounding
        # degraded with ring size)
        dq, dk, dv, _ = _bwd_impl(
            q, k, v, o, lse, do, scale, causal,
            _chunk_shape(q, k, v, causal, block_q, block_k),
            dropout_rate, seed, None, q_seg, kv_seg,
            grad_dtype=jnp.float32, q_off=q_off, k_off=k_off)
        return dq, dk, dv
    return _chunk_bwd_jnp(q, k, v, do, lse, delta, scale, causal,
                          q_seg, kv_seg, block_k, dropout_rate, seed,
                          q_off, k_off)


# ------------------------------- ring core ----------------------------------

def _merge(o_acc, lse_acc, o_c, lse_c):
    """Merge a chunk's normalized (o, lse) into the running state —
    the cross-chip half of online softmax."""
    m = jnp.maximum(lse_acc, lse_c)
    w1 = jnp.exp(lse_acc - m)
    w2 = jnp.exp(lse_c - m)
    wsum = w1 + w2
    o = (o_acc * w1[..., None] + o_c.astype(jnp.float32) * w2[..., None]
         ) / wsum[..., None]
    return o, m + jnp.log(wsum)


def _int_zero(x):
    """float0 cotangent for integer (segment-id) primals — the one
    convention both ring variants share."""
    return (None if x is None
            else np.zeros(x.shape, dtype=jax.dtypes.float0))


def _rotate(axis_name, n, tree):
    perm = [(r, (r + 1) % n) for r in range(n)]
    return jax.tree_util.tree_map(
        lambda x: lax.ppermute(x, axis_name, perm), tree)


def _ring_fwd_impl(q, k, v, q_seg, kv_seg, seed, axis_name, causal,
                   scale, block_q, block_k, pallas_path, dropout_rate):
    b, h, s, d = q.shape
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    has_seg = q_seg is not None

    def step(carry, i):
        o_acc, lse_acc, k_c, v_c, kseg_c = carry
        src = (rank - i) % n
        kseg_arg = kseg_c if has_seg else None

        def attend(k_c, v_c, kseg_c, diag):
            # global offsets make the coordinate-hash dropout mask agree
            # across ring steps AND with single-chip attention over the
            # gathered sequence
            return _chunk_fwd(q, k_c, v_c, scale, causal and diag, q_seg,
                              kseg_c, block_q, block_k, pallas_path,
                              dropout_rate, seed, rank * s, src * s)
        if causal:
            # strictly-above-diagonal chunks (src > rank) are fully
            # masked: the skip branch runs NO score work — a causal
            # ring does ~half the FLOPs of a full ring
            def do_skip(_):
                return o_acc, lse_acc

            def do_diag(_):
                return _merge(o_acc, lse_acc,
                              *attend(k_c, v_c, kseg_arg, True))

            def do_full(_):
                return _merge(o_acc, lse_acc,
                              *attend(k_c, v_c, kseg_arg, False))

            idx = jnp.where(src > rank, 0, jnp.where(src == rank, 1, 2))
            o_acc, lse_acc = lax.switch(idx, (do_skip, do_diag, do_full),
                                        None)
        else:
            o_acc, lse_acc = _merge(o_acc, lse_acc,
                                    *attend(k_c, v_c, kseg_arg, False))
        k_c, v_c = _rotate(axis_name, n, (k_c, v_c))
        if has_seg:
            kseg_c = _rotate(axis_name, n, kseg_c)
        return (o_acc, lse_acc, k_c, v_c, kseg_c), None

    o0 = jnp.zeros((b, h, s, d), jnp.float32)
    lse0 = jnp.full((b, h, s), _NEG_INF, jnp.float32)
    kseg0 = kv_seg if has_seg else jnp.zeros((), jnp.int32)
    (o, lse, *_), _ = lax.scan(step, (o0, lse0, k, v, kseg0),
                               jnp.arange(n))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11,
                                                    12))
def _ring(q, k, v, q_seg, kv_seg, seed, axis_name, causal, scale,
          block_q, block_k, pallas_path, dropout_rate):
    o, _ = _ring_fwd_impl(q, k, v, q_seg, kv_seg, seed, axis_name,
                          causal, scale, block_q, block_k, pallas_path,
                          dropout_rate)
    return o


def _ring_vjp_fwd(q, k, v, q_seg, kv_seg, seed, axis_name, causal,
                  scale, block_q, block_k, pallas_path, dropout_rate):
    o, lse = _ring_fwd_impl(q, k, v, q_seg, kv_seg, seed, axis_name,
                            causal, scale, block_q, block_k, pallas_path,
                            dropout_rate)
    # residuals are O(s_local · d) per device — blockwise recompute in
    # backward replaces AD-through-scan's O(n · s_local²) saved scores
    return o, (q, k, v, q_seg, kv_seg, seed, o, lse)


def _ring_vjp_bwd(axis_name, causal, scale, block_q, block_k, pallas_path,
                  dropout_rate, res, do):
    q, k, v, q_seg, kv_seg, seed, o, lse = res
    s = q.shape[2]
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    has_seg = q_seg is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    zero_kd = jnp.zeros(k.shape, jnp.float32)

    def step(carry, i):
        # dk/dv accumulators TRAVEL with their kv chunk: after n
        # rotations each has collected every rank's contribution and is
        # back home (≡ ring-attention backward; no gather of n shards)
        dq_acc, k_c, v_c, kseg_c, dk_c, dv_c = carry
        src = (rank - i) % n
        kseg_arg = kseg_c if has_seg else None

        def partials(k_c, v_c, kseg_c, diag):
            return _chunk_bwd(q, k_c, v_c, o, lse, delta, do, scale,
                              causal and diag, q_seg, kseg_c, block_q,
                              block_k, pallas_path, dropout_rate, seed,
                              rank * s, src * s)
        if causal:
            def do_skip(_):
                return (jnp.zeros(q.shape, jnp.float32), zero_kd, zero_kd)

            def do_diag(_):
                return partials(k_c, v_c, kseg_arg, True)

            def do_full(_):
                return partials(k_c, v_c, kseg_arg, False)

            idx = jnp.where(src > rank, 0, jnp.where(src == rank, 1, 2))
            dq_p, dk_p, dv_p = lax.switch(
                idx, (do_skip, do_diag, do_full), None)
        else:
            dq_p, dk_p, dv_p = partials(k_c, v_c, kseg_arg, False)
        dq_acc = dq_acc + dq_p
        dk_c = dk_c + dk_p
        dv_c = dv_c + dv_p
        k_c, v_c, dk_c, dv_c = _rotate(axis_name, n,
                                       (k_c, v_c, dk_c, dv_c))
        if has_seg:
            kseg_c = _rotate(axis_name, n, kseg_c)
        return (dq_acc, k_c, v_c, kseg_c, dk_c, dv_c), None

    kseg0 = kv_seg if has_seg else jnp.zeros((), jnp.int32)
    carry0 = (jnp.zeros(q.shape, jnp.float32), k, v, kseg0,
              zero_kd, zero_kd)
    (dq, _, _, _, dk, dv), _ = lax.scan(step, carry0, jnp.arange(n))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            _int_zero(q_seg), _int_zero(kv_seg), _int_zero(seed))


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


# ------------------- zigzag ring (load-balanced causal) ---------------------
#
# The contiguous causal ring SKIPS above-diagonal chunks, which halves
# total FLOPs but not the critical path: rank n-1 computes at every one
# of the n steps while rank 0 computes once.  Zigzag sharding fixes the
# balance: split the global sequence into 2n half-chunks and give
# device r the PAIR (r, 2n-1-r) — one early half ("a") and one late
# half ("b").  Visiting kv from src carries halves (c=src, d=2n-1-src);
# the causal block structure then decomposes per step into
#   (a,c): skip if src>r, diag if src==r, full if src<r
#   (a,d): always skip          (d ≥ n > a — kv strictly later)
#   (b,c): always full          (c ≤ n-1 < n ≤ b)
#   (b,d): skip if src<r, diag if src==r, full if src>r
# so EVERY device runs exactly two half-computes per step (three on its
# single diagonal step): per-step work is uniform across ranks and the
# causal ring's wall-clock halves vs the contiguous layout.

def _zigzag_perm(n, seq_len):
    """Global positions in zigzag order: device r's contiguous shard is
    global half-chunks (r, 2n-1-r)."""
    if seq_len % (2 * n):
        raise ValueError(
            f"zigzag needs seq_len % (2*n) == 0, got {seq_len} % {2 * n}")
    c = seq_len // (2 * n)
    return np.concatenate([
        np.r_[r * c:(r + 1) * c, (2 * n - 1 - r) * c:(2 * n - r) * c]
        for r in range(n)])


def zigzag_shard(x, n, axis=2):
    """Reorder a GLOBAL sequence axis so a contiguous n-way shard_map
    split gives device r the zigzag pair (r, 2n-1-r).  seq % 2n == 0."""
    return jnp.take(x, jnp.asarray(_zigzag_perm(n, x.shape[axis])),
                    axis=axis)


def zigzag_unshard(x, n, axis=2):
    """Inverse of zigzag_shard."""
    perm = _zigzag_perm(n, x.shape[axis])
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.shape[0])
    return jnp.take(x, jnp.asarray(inv), axis=axis)


def _halves(x, half, axis=2):
    if x is None:
        return None, None
    lo = lax.slice_in_dim(x, 0, half, axis=axis)
    hi = lax.slice_in_dim(x, half, x.shape[axis], axis=axis)
    return lo, hi


def _ring_fwd_zigzag(q, k, v, q_seg, kv_seg, seed, axis_name, scale,
                     block_q, block_k, pallas_path, dropout_rate):
    b, h, s, d = q.shape
    half = s // 2
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    has_seg = q_seg is not None
    q_a, q_b = _halves(q, half)
    qs_a, qs_b = _halves(q_seg, half, axis=1)
    # GLOBAL half-chunk offsets (zigzag order: device r owns halves
    # (r, 2n-1-r)) feed the coordinate-hash dropout so the mask agrees
    # across steps and with the gathered-sequence single-chip mask
    qo_a = rank * half
    qo_b = (2 * n - 1 - rank) * half

    def attend(qh, qsh, kh, vh, ksh, causal_flag, q_off, k_off):
        return _chunk_fwd(qh, kh, vh, scale, causal_flag, qsh, ksh,
                          block_q, block_k, pallas_path, dropout_rate,
                          seed, q_off, k_off)

    def gated(idx, o_acc, l_acc, qh, qsh, kh, vh, ksh, q_off, k_off):
        """idx: 0 skip, 1 diag (causal), 2 full."""
        def do_skip(_):
            return o_acc, l_acc

        def do_diag(_):
            return _merge(o_acc, l_acc, *attend(qh, qsh, kh, vh, ksh,
                                                True, q_off, k_off))

        def do_full(_):
            return _merge(o_acc, l_acc, *attend(qh, qsh, kh, vh, ksh,
                                                False, q_off, k_off))

        return lax.switch(idx, (do_skip, do_diag, do_full), None)

    def step(carry, i):
        o_a, l_a, o_b, l_b, k_c, v_c, kseg_c = carry
        src = (rank - i) % n
        ko_lo = src * half
        ko_hi = (2 * n - 1 - src) * half
        k_lo, k_hi = _halves(k_c, half)
        v_lo, v_hi = _halves(v_c, half)
        ks_lo, ks_hi = _halves(kseg_c if has_seg else None, half, axis=1)
        # (b, c): unconditionally full
        o_b, l_b = _merge(o_b, l_b,
                          *attend(q_b, qs_b, k_lo, v_lo, ks_lo, False,
                                  qo_b, ko_lo))
        # (a, c)
        idx_ac = jnp.where(src > rank, 0, jnp.where(src == rank, 1, 2))
        o_a, l_a = gated(idx_ac, o_a, l_a, q_a, qs_a, k_lo, v_lo, ks_lo,
                         qo_a, ko_lo)
        # (b, d)
        idx_bd = jnp.where(src < rank, 0, jnp.where(src == rank, 1, 2))
        o_b, l_b = gated(idx_bd, o_b, l_b, q_b, qs_b, k_hi, v_hi, ks_hi,
                         qo_b, ko_hi)
        k_c, v_c = _rotate(axis_name, n, (k_c, v_c))
        if has_seg:
            kseg_c = _rotate(axis_name, n, kseg_c)
        return (o_a, l_a, o_b, l_b, k_c, v_c, kseg_c), None

    o0 = jnp.zeros((b, h, half, d), jnp.float32)
    l0 = jnp.full((b, h, half), _NEG_INF, jnp.float32)
    kseg0 = kv_seg if has_seg else jnp.zeros((), jnp.int32)
    (o_a, l_a, o_b, l_b, *_), _ = lax.scan(
        step, (o0, l0, o0, l0, k, v, kseg0), jnp.arange(n))
    o = jnp.concatenate([o_a, o_b], axis=2).astype(q.dtype)
    lse = jnp.concatenate([l_a, l_b], axis=2)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _ring_zz(q, k, v, q_seg, kv_seg, seed, axis_name, scale, block_q,
             block_k, pallas_path, dropout_rate):
    o, _ = _ring_fwd_zigzag(q, k, v, q_seg, kv_seg, seed, axis_name,
                            scale, block_q, block_k, pallas_path,
                            dropout_rate)
    return o


def _ring_zz_vjp_fwd(q, k, v, q_seg, kv_seg, seed, axis_name, scale,
                     block_q, block_k, pallas_path, dropout_rate):
    o, lse = _ring_fwd_zigzag(q, k, v, q_seg, kv_seg, seed, axis_name,
                              scale, block_q, block_k, pallas_path,
                              dropout_rate)
    return o, (q, k, v, q_seg, kv_seg, seed, o, lse)


def _ring_zz_vjp_bwd(axis_name, scale, block_q, block_k, pallas_path,
                     dropout_rate, res, do):
    q, k, v, q_seg, kv_seg, seed, o, lse = res
    half = q.shape[2] // 2
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    has_seg = q_seg is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)
    q_a, q_b = _halves(q, half)
    o_a, o_b = _halves(o, half)
    do_a, do_b = _halves(do, half)
    qs_a, qs_b = _halves(q_seg, half, axis=1)
    lse_a, lse_b = _halves(lse, half, axis=2)
    d_a, d_b = _halves(delta, half, axis=2)
    qo_a = rank * half
    qo_b = (2 * n - 1 - rank) * half
    # q and kv shards share (b, h, half, d) — one zero serves the skip
    # branch's dq, dk, and dv partials
    zero_half = jnp.zeros(q_a.shape, jnp.float32)

    def step(carry, i):
        (dq_a, dq_b, k_c, v_c, kseg_c,
         dk_lo, dk_hi, dv_lo, dv_hi) = carry
        src = (rank - i) % n
        ko_lo = src * half
        ko_hi = (2 * n - 1 - src) * half
        k_lo, k_hi = _halves(k_c, half)
        v_lo, v_hi = _halves(v_c, half)
        ks_lo, ks_hi = _halves(kseg_c if has_seg else None, half, axis=1)

        def partials(qh, qsh, oh, lh, dh, doh, kh, vh, ksh, causal_flag,
                     q_off, k_off):
            return _chunk_bwd(qh, kh, vh, oh, lh, dh, doh, scale,
                              causal_flag, qsh, ksh, block_q, block_k,
                              pallas_path, dropout_rate, seed, q_off,
                              k_off)

        def gated(idx, *args):
            def do_skip(_):
                return zero_half, zero_half, zero_half

            def do_diag(_):
                return partials(*args[:-2], True, *args[-2:])

            def do_full(_):
                return partials(*args[:-2], False, *args[-2:])

            return lax.switch(idx, (do_skip, do_diag, do_full), None)

        # (b, c): unconditionally full
        p_q, p_k, p_v = partials(q_b, qs_b, o_b, lse_b, d_b, do_b,
                                 k_lo, v_lo, ks_lo, False, qo_b, ko_lo)
        dq_b = dq_b + p_q
        dk_lo = dk_lo + p_k
        dv_lo = dv_lo + p_v
        # (a, c)
        idx_ac = jnp.where(src > rank, 0, jnp.where(src == rank, 1, 2))
        p_q, p_k, p_v = gated(idx_ac, q_a, qs_a, o_a, lse_a, d_a, do_a,
                              k_lo, v_lo, ks_lo, qo_a, ko_lo)
        dq_a = dq_a + p_q
        dk_lo = dk_lo + p_k
        dv_lo = dv_lo + p_v
        # (b, d)
        idx_bd = jnp.where(src < rank, 0, jnp.where(src == rank, 1, 2))
        p_q, p_k, p_v = gated(idx_bd, q_b, qs_b, o_b, lse_b, d_b, do_b,
                              k_hi, v_hi, ks_hi, qo_b, ko_hi)
        dq_b = dq_b + p_q
        dk_hi = dk_hi + p_k
        dv_hi = dv_hi + p_v
        (k_c, v_c, dk_lo, dk_hi, dv_lo, dv_hi) = _rotate(
            axis_name, n, (k_c, v_c, dk_lo, dk_hi, dv_lo, dv_hi))
        if has_seg:
            kseg_c = _rotate(axis_name, n, kseg_c)
        return (dq_a, dq_b, k_c, v_c, kseg_c,
                dk_lo, dk_hi, dv_lo, dv_hi), None

    kseg0 = kv_seg if has_seg else jnp.zeros((), jnp.int32)
    carry0 = (zero_half, zero_half, k, v, kseg0,
              zero_half, zero_half, zero_half, zero_half)
    (dq_a, dq_b, _, _, _, dk_lo, dk_hi, dv_lo, dv_hi), _ = lax.scan(
        step, carry0, jnp.arange(n))
    dq = jnp.concatenate([dq_a, dq_b], axis=2)
    dk = jnp.concatenate([dk_lo, dk_hi], axis=2)
    dv = jnp.concatenate([dv_lo, dv_hi], axis=2)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            _int_zero(q_seg), _int_zero(kv_seg), _int_zero(seed))


_ring_zz.defvjp(_ring_zz_vjp_fwd, _ring_zz_vjp_bwd)


# -------------------------------- public API --------------------------------

def ring_attention(q, k, v, axis_name: str, *, causal: bool = False,
                   softmax_scale: Optional[float] = None,
                   segment_ids=None, q_segment_ids=None,
                   kv_segment_ids=None,
                   layout: str = "contiguous",
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   dropout_rate: float = 0.0,
                   dropout_key=None,
                   use_pallas_override: Optional[bool] = None):
    """Blockwise ring attention (see module docstring for the design).

    q, k, v: (b, h, s_local, d) — the LOCAL sequence shard; the global
    sequence is the concatenation over the axis in rank order.  Segment
    ids are (b, s_local) int per shard, global semantics (tokens attend
    only within equal ids, across shards).  Returns the local output
    shard (b, h, s_local, d).

    layout="zigzag" (causal only): device r holds the global half-chunk
    PAIR (r, 2n-1-r) — shard with `zigzag_shard` (and undo with
    `zigzag_unshard`).  Every device then runs exactly two half-chunk
    computes per ring step, so the causal ring's wall-clock HALVES vs
    the contiguous layout, whose last rank computes at every step (see
    the zigzag section above).  Non-causal attention has no positional
    structure to balance — use the default layout.

    dropout_rate / dropout_key: in-kernel attention dropout.  The
    coordinate-hash keep mask uses each chunk's GLOBAL (q, k) offsets,
    so every ring step (and the backward) sees one consistent global
    mask — identical bits to single-chip flash attention over the
    gathered sequence with the same key.  Pass the SAME key on every
    device (it is replicated state, like the params).
    """
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    d = q.shape[-1]
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / math.sqrt(d))
    if segment_ids is not None:
        if q_segment_ids is not None or kv_segment_ids is not None:
            raise ValueError(
                "pass either segment_ids or q_/kv_segment_ids, not both")
        q_segment_ids = kv_segment_ids = segment_ids
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("q_segment_ids and kv_segment_ids go together")
    b, s = q.shape[0], q.shape[2]
    seed = None
    if dropout_rate > 0.0:
        if dropout_key is None:
            raise ValueError("dropout_rate > 0 needs a dropout_key")
        seed = jax.random.randint(dropout_key, (1, 1), -2 ** 31,
                                  2 ** 31 - 1, dtype=jnp.int32)
    if q_segment_ids is not None:
        q_segment_ids = jnp.asarray(q_segment_ids, jnp.int32)
        kv_segment_ids = jnp.asarray(kv_segment_ids, jnp.int32)
        if (q_segment_ids.shape != (b, s)
                or kv_segment_ids.shape != (b, s)):
            raise ValueError(
                f"segment id shapes {q_segment_ids.shape}/"
                f"{kv_segment_ids.shape} != ({b}, {s})")
    if layout == "zigzag":
        if not causal:
            raise ValueError(
                "layout='zigzag' is causal-only: non-causal attention "
                "has no positional imbalance to fix — use the default "
                "contiguous layout (results are identical)")
        if s % 2:
            raise ValueError("zigzag needs an even local sequence")
        pallas_path = bool(use_pallas(use_pallas_override)
                           and _pick_block(s // 2))
        return _ring_zz(q, k, v, q_segment_ids, kv_segment_ids, seed,
                        axis_name, scale, block_q, block_k, pallas_path,
                        float(dropout_rate))
    pallas_path = bool(use_pallas(use_pallas_override)
                       and _pick_block(s))
    return _ring(q, k, v, q_segment_ids, kv_segment_ids, seed, axis_name,
                 causal, scale, block_q, block_k, pallas_path,
                 float(dropout_rate))


def ulysses_attention(q, k, v, axis_name: str, *, causal: bool = False,
                      softmax_scale: Optional[float] = None,
                      segment_ids=None,
                      use_flash: bool = True,
                      use_pallas_override: Optional[bool] = None):
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

    Inputs are seq-sharded (b, h, s_local, d) with h % axis_size == 0;
    internally heads are scattered so each device sees the FULL sequence
    for h/axis heads, runs (flash) attention, and scatters back.
    segment_ids: (b, s_local) int per shard, global semantics — gathered
    to the full sequence with the heads (packed-varlen works here too).
    """
    n = lax.axis_size(axis_name)
    b, h, s_local, d = q.shape
    assert h % n == 0, "ulysses needs heads divisible by the axis size"

    def seq_to_heads(x):
        # (b, h, s_local, d) → (b, h/n, s_global, d): scatter heads,
        # gather sequence — one tiled all_to_all
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    seg_g = None
    if segment_ids is not None:
        # every device needs the FULL (b, s_global) ids — one gather
        seg_g = lax.all_gather(jnp.asarray(segment_ids, jnp.int32),
                               axis_name, axis=1, tiled=True)
    if use_flash:
        from apex_tpu.ops.flash_attention import flash_attention
        og = flash_attention(qg, kg, vg, causal=causal,
                             softmax_scale=softmax_scale,
                             segment_ids=seg_g,
                             use_pallas_override=use_pallas_override)
    else:
        from apex_tpu.ops.flash_attention import attention_reference
        og = attention_reference(qg, kg, vg, causal=causal,
                                 softmax_scale=softmax_scale,
                                 q_segment_ids=seg_g, kv_segment_ids=seg_g)
    return heads_to_seq(og)
