"""Fused scaled-(masked)-softmax family — Pallas fwd+bwd.

≡ the reference's four Megatron softmax extensions:
  scaled_upper_triang_masked_softmax_cuda (csrc/megatron/scaled_upper_triang_masked_softmax.cpp)
  scaled_masked_softmax_cuda              (csrc/megatron/scaled_masked_softmax.cpp)
  generic_scaled_masked_softmax_cuda      (csrc/megatron/generic_scaled_masked_softmax.cpp)
  scaled_softmax_cuda                     (csrc/megatron/scaled_softmax.cpp)
and their autograd wrappers (apex/transformer/functional/fused_softmax.py:21-276).

One blocked Pallas kernel covers all variants (the CUDA split into
warp-tuned vs "generic" shapes is a GPU artifact; on TPU a single
row-blocked kernel serves every sequence length).  Mask semantics match
the reference: masked positions receive -10000 before the softmax
(masked_fill_, scaled_masked_softmax.h), so fully-masked rows produce a
uniform distribution, not NaN.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from apex_tpu.monitor.compile.startup import kernel_span
from apex_tpu.ops._common import (pallas_interpret, row_block,
                                  tuned_row_block, use_pallas)

_MASK_VALUE = -10000.0


# --------------------------- reference (jnp) path ---------------------------

def scaled_softmax_reference(x, scale=1.0):
    x32 = x.astype(jnp.float32) * scale
    return jax.nn.softmax(x32, axis=-1).astype(x.dtype)


def scaled_masked_softmax_reference(x, mask, scale=1.0):
    """mask: bool, True = masked out (≡ reference mask semantics)."""
    x32 = x.astype(jnp.float32) * scale
    x32 = jnp.where(mask, _MASK_VALUE, x32)
    return jax.nn.softmax(x32, axis=-1).astype(x.dtype)


def scaled_upper_triang_masked_softmax_reference(x, scale=1.0):
    """Causal mask over the last two dims (sq, sk), sq == sk."""
    sq, sk = x.shape[-2], x.shape[-1]
    causal = jnp.triu(jnp.ones((sq, sk), bool), k=1)
    return scaled_masked_softmax_reference(x, causal, scale)


# ------------------------------ pallas kernels ------------------------------

def _fwd_kernel(x_ref, m_ref, y_ref, *, scale, causal, has_mask, sq, blk):
    x = x_ref[...].astype(jnp.float32) * scale
    if has_mask:
        x = jnp.where(m_ref[...], _MASK_VALUE, x)
    if causal:
        i = pl.program_id(0)
        rows = i * blk + lax.broadcasted_iota(jnp.int32, x.shape, 0)
        pos = rows % sq
        cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(cols > pos, _MASK_VALUE, x)
    x = x - jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x)
    y = e / jnp.sum(e, axis=1, keepdims=True)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(g_ref, y_ref, dx_ref, *, scale):
    g = g_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    dot = jnp.sum(g * y, axis=1, keepdims=True)
    dx_ref[...] = (scale * y * (g - dot)).astype(dx_ref.dtype)


def _pad_rows(a, blk):
    pad = (-a.shape[0]) % blk
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
    return a


def _fwd_pallas(x2, mask2, scale, causal, sq):
    rows, sk = x2.shape
    has_mask = mask2 is not None
    blk = tuned_row_block("softmax_fwd", rows, sk)
    xp = _pad_rows(x2, blk)
    prows = xp.shape[0]
    grid = prows // blk
    inputs = [xp]
    in_specs = [pl.BlockSpec((blk, sk), lambda i: (i, 0))]
    if has_mask:
        inputs.append(_pad_rows(mask2, blk))
        in_specs.append(pl.BlockSpec((blk, sk), lambda i: (i, 0)))
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               has_mask=has_mask, sq=sq, blk=blk)

    def wrapped(x_ref, *rest):
        if has_mask:
            m_ref, y_ref = rest
        else:
            (y_ref,) = rest
            m_ref = None
        kernel(x_ref, m_ref, y_ref)

    with kernel_span("softmax_fwd"):
        y = pl.pallas_call(
            wrapped,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((blk, sk), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((prows, sk), x2.dtype),
            interpret=pallas_interpret(),
            name="softmax_fwd",
        )(*inputs)
    return y[:rows]


def _bwd_pallas(g2, y2, scale):
    rows, sk = g2.shape
    blk = tuned_row_block("softmax_bwd", rows, sk)
    gp, yp = _pad_rows(g2, blk), _pad_rows(y2, blk)
    prows = gp.shape[0]
    with kernel_span("softmax_bwd"):
        dx = pl.pallas_call(
            functools.partial(_bwd_kernel, scale=scale),
            grid=(prows // blk,),
            in_specs=[pl.BlockSpec((blk, sk), lambda i: (i, 0)),
                      pl.BlockSpec((blk, sk), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((blk, sk), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((prows, sk), g2.dtype),
            interpret=pallas_interpret(),
            name="softmax_bwd",
        )(gp, yp)
    return dx[:rows]


# ----------------------------- custom_vjp plumbing --------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _softmax(x, mask, scale, causal):
    return _softmax_impl(x, mask, scale, causal)


def _softmax_impl(x, mask, scale, causal):
    shape = x.shape
    sk = shape[-1]
    sq = shape[-2] if len(shape) >= 2 else 1
    x2 = x.reshape(-1, sk)
    mask2 = None
    if mask is not None:
        mask2 = jnp.broadcast_to(mask, shape).reshape(-1, sk)
    return _fwd_pallas(x2, mask2, scale, causal, sq).reshape(shape)


def _softmax_fwd(x, mask, scale, causal):
    y = _softmax_impl(x, mask, scale, causal)
    return y, y


def _softmax_bwd(scale, causal, y, g):
    shape = y.shape
    dx = _bwd_pallas(g.reshape(-1, shape[-1]), y.reshape(-1, shape[-1]), scale)
    return (dx.reshape(shape), None)


_softmax.defvjp(_softmax_fwd, _softmax_bwd)


# --------------------------------- public API -------------------------------

def scaled_softmax(x, scale: float = 1.0,
                   use_pallas_override: Optional[bool] = None):
    """≡ ScaledSoftmax (fused_softmax.py:180-216)."""
    if use_pallas(use_pallas_override):
        return _softmax(x, None, float(scale), False)
    return scaled_softmax_reference(x, scale)


def scaled_masked_softmax(x, mask, scale: float = 1.0,
                          use_pallas_override: Optional[bool] = None):
    """≡ ScaledMaskedSoftmax (fused_softmax.py:94-130); also covers the
    GenericScaledMaskedSoftmax arbitrary-shape variant (132-163)."""
    if mask is None:
        return scaled_softmax(x, scale, use_pallas_override)
    if use_pallas(use_pallas_override):
        return _softmax(x, mask, float(scale), False)
    return scaled_masked_softmax_reference(x, mask, scale)


def scaled_upper_triang_masked_softmax(x, scale: float = 1.0,
                                       use_pallas_override: Optional[bool] = None):
    """≡ ScaledUpperTriangMaskedSoftmax (fused_softmax.py:21-56)."""
    if x.shape[-2] != x.shape[-1]:
        raise ValueError("causal softmax requires sq == sk")
    if use_pallas(use_pallas_override):
        return _softmax(x, None, float(scale), True)
    return scaled_upper_triang_masked_softmax_reference(x, scale)


def get_batch_per_block(sq: int, sk: int, batches: int, attn_heads: int) -> int:
    """Scheduling hint ≡ scaled_masked_softmax_cuda.get_batch_per_block
    (csrc/megatron/scaled_masked_softmax.cpp): how many (batch, head)
    rows one kernel block covers.  The Pallas kernel tiles rows in
    row-block groups over the flattened (batches*heads*sq) dimension,
    so the answer is rows-per-block / sq (at least 1)."""
    rows = batches * attn_heads * sq
    return max(1, row_block(rows, sk) // max(sq, 1))
