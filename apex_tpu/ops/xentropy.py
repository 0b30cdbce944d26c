"""Fused label-smoothed softmax cross-entropy — Pallas fwd+bwd.

≡ the reference's `xentropy_cuda` extension
(apex/contrib/csrc/xentropy/xentropy_kernel.cu, 718 LoC) and its wrapper
`apex.contrib.xentropy.SoftmaxCrossEntropyLoss` (apex/contrib/xentropy/__init__.py:1):
one pass computes per-sample loss = lse(x) - (1-eps)*x[label] - eps*mean(x)
saving only the log-sum-exp for backward; the backward pass reconstructs
softmax(x) - q where q = (1-eps)*onehot + eps/V.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from apex_tpu.monitor.compile.startup import kernel_span
from apex_tpu.ops._common import pallas_interpret, row_block, use_pallas


# --------------------------- reference (jnp) path ---------------------------

def softmax_cross_entropy_reference(logits, labels, smoothing=0.0):
    """Per-sample loss, fp32; labels int (rows,)."""
    x = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(x, axis=-1)
    xl = jnp.take_along_axis(x, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    if smoothing:
        return lse - (1.0 - smoothing) * xl - smoothing * jnp.mean(x, axis=-1)
    return lse - xl


# ------------------------------ pallas kernels ------------------------------

def _fwd_kernel(x_ref, lbl_ref, loss_ref, lse_ref, *, smoothing):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=1, keepdims=True)) + m
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (cols == lbl_ref[...]).astype(jnp.float32)
    xl = jnp.sum(x * onehot, axis=1, keepdims=True)
    loss = lse - (1.0 - smoothing) * xl
    if smoothing:
        loss = loss - smoothing * jnp.mean(x, axis=1, keepdims=True)
    loss_ref[...] = loss
    lse_ref[...] = lse


def _bwd_kernel(g_ref, x_ref, lbl_ref, lse_ref, dx_ref, *, smoothing):
    x = x_ref[...].astype(jnp.float32)
    p = jnp.exp(x - lse_ref[...])
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (cols == lbl_ref[...]).astype(jnp.float32)
    q = (1.0 - smoothing) * onehot
    if smoothing:
        q = q + smoothing / x.shape[1]
    dx_ref[...] = (g_ref[...] * (p - q)).astype(dx_ref.dtype)


def _pad(a, blk, fill=0):
    pad = (-a.shape[0]) % blk
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=fill)
    return a


def _fwd_pallas(x2, labels, smoothing):
    rows, v = x2.shape
    blk = row_block(rows, v)
    xp = _pad(x2, blk)
    lbl = _pad(labels.astype(jnp.int32).reshape(-1, 1), blk)
    prows = xp.shape[0]
    with kernel_span("xent_fwd"):
        loss, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, smoothing=smoothing),
            grid=(prows // blk,),
            in_specs=[pl.BlockSpec((blk, v), lambda i: (i, 0)),
                      pl.BlockSpec((blk, 1), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                       pl.BlockSpec((blk, 1), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((prows, 1), jnp.float32),
                       jax.ShapeDtypeStruct((prows, 1), jnp.float32)],
            interpret=pallas_interpret(),
            name="xent_fwd",
        )(xp, lbl)
    return loss[:rows, 0], lse[:rows]


def _bwd_pallas(g, x2, labels, lse, smoothing):
    rows, v = x2.shape
    blk = row_block(rows, v)
    gp = _pad(g.reshape(-1, 1).astype(jnp.float32), blk)
    xp = _pad(x2, blk)
    lbl = _pad(labels.astype(jnp.int32).reshape(-1, 1), blk)
    lsep = _pad(lse, blk)
    prows = xp.shape[0]
    with kernel_span("xent_bwd"):
        dx = pl.pallas_call(
            functools.partial(_bwd_kernel, smoothing=smoothing),
            grid=(prows // blk,),
            in_specs=[pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                      pl.BlockSpec((blk, v), lambda i: (i, 0)),
                      pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                      pl.BlockSpec((blk, 1), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((blk, v), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((prows, v), x2.dtype),
            interpret=pallas_interpret(),
            name="xent_bwd",
        )(gp, xp, lbl, lsep)
    return dx[:rows]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _xent(logits2, labels, smoothing):
    loss, _ = _fwd_pallas(logits2, labels, smoothing)
    return loss


def _xent_fwd(logits2, labels, smoothing):
    loss, lse = _fwd_pallas(logits2, labels, smoothing)
    return loss, (logits2, labels, lse)


def _xent_bwd(smoothing, res, g):
    logits2, labels, lse = res
    return (_bwd_pallas(g, logits2, labels, lse, smoothing), None)


_xent.defvjp(_xent_fwd, _xent_bwd)


# --------------------------------- public API -------------------------------

def softmax_cross_entropy_loss(logits, labels, smoothing: float = 0.0,
                               use_pallas_override: Optional[bool] = None):
    """Per-sample label-smoothed cross entropy.

    ≡ apex.contrib.xentropy.SoftmaxCrossEntropyLoss.apply(logits, labels,
    smoothing, padding_idx=0, half_to_float).  Leading dims are batch;
    last dim is the vocab.
    """
    shape = logits.shape
    if use_pallas(use_pallas_override):
        loss = _xent(logits.reshape(-1, shape[-1]), labels.reshape(-1),
                     float(smoothing))
        return loss.reshape(shape[:-1])
    return softmax_cross_entropy_reference(logits, labels, smoothing)


SoftmaxCrossEntropyLoss = softmax_cross_entropy_loss
