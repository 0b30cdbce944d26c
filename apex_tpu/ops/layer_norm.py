"""Fused LayerNorm / RMSNorm — Pallas fwd+bwd with custom_vjp.

≡ the reference's `fused_layer_norm_cuda` extension
(csrc/layer_norm_cuda.cpp:429-441, kernels csrc/layer_norm_cuda_kernel.cu:411-678)
and its Python wrappers (apex/normalization/fused_layer_norm.py:32-165):
fwd/bwd × {affine, plain} × {LayerNorm, RMSNorm}, computing statistics in
fp32 regardless of input dtype (the "mixed dtype" Megatron variants fall
out for free — stats are always fp32 here) and saving (mean, rstd) for
backward.  Also subsumes `apex.contrib.layer_norm.FastLayerNorm`
(apex/contrib/layer_norm/layer_norm.py:40) — on TPU one blocked kernel
covers all hidden sizes instead of per-size tuned CUDA kernels.

Layout: leading dims are flattened to rows; the kernel grids over row
blocks with the full hidden dim resident in VMEM (hidden ≤ ~64k fp32).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from apex_tpu.monitor.compile.startup import kernel_span
from apex_tpu.ops._common import (pallas_interpret, tuned_row_block,
                                  use_pallas_fusable)


# --------------------------- reference (jnp) path ---------------------------

def layer_norm_reference(x, weight=None, bias=None, eps=1e-5):
    """Pure-jnp LayerNorm over the last dim, fp32 stats (the CPU fallback,
    ≡ apex/normalization/fused_layer_norm.py:288-294)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_reference(x, weight=None, eps=1e-5):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(ms + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


# ------------------------------ pallas kernels ------------------------------

def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps, rms,
                affine, has_bias):
    x = x_ref[...].astype(jnp.float32)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(x * x, axis=1, keepdims=True)
    else:
        mean = jnp.mean(x, axis=1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    if affine:
        y = y * w_ref[...].astype(jnp.float32)
        if has_bias:
            y = y + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(g_ref, x_ref, mean_ref, rstd_ref, w_ref,
                dx_ref, dw_ref, db_ref, *, rms, affine):
    g = g_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    if affine:
        wg = g * w_ref[...].astype(jnp.float32)
    else:
        wg = g
    # dx = rstd * (wg - mean(wg)[LN only] - xhat * mean(wg * xhat))
    c2 = jnp.mean(wg * xhat, axis=1, keepdims=True)
    if rms:
        dx = rstd * (wg - xhat * c2)
    else:
        c1 = jnp.mean(wg, axis=1, keepdims=True)
        dx = rstd * (wg - c1 - xhat * c2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    if affine:
        # accumulate dw/db across the sequential grid (single (1, hidden)
        # output revisited every step — TPU grids are sequential)
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            dw_ref[...] = jnp.zeros_like(dw_ref)
            db_ref[...] = jnp.zeros_like(db_ref)

        dw_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)
        db_ref[...] += jnp.sum(g, axis=0, keepdims=True)


def _pad_rows(x2, block):
    rows = x2.shape[0]
    pad = (-rows) % block
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, rows


def _fwd_pallas(x2, weight, bias, eps, rms):
    rows, hidden = x2.shape
    affine = weight is not None
    has_bias = bias is not None
    blk = tuned_row_block("layer_norm_fwd", rows, hidden)
    x2p, _ = _pad_rows(x2, blk)
    prows = x2p.shape[0]
    grid = prows // blk
    w = weight if affine else jnp.zeros((hidden,), x2.dtype)
    b = bias if has_bias else jnp.zeros((hidden,), x2.dtype)
    kernel = functools.partial(_fwd_kernel, eps=eps, rms=rms, affine=affine,
                               has_bias=has_bias)
    with kernel_span("ln_fwd"):
        y, mean, rstd = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((blk, hidden), lambda i: (i, 0)),
                pl.BlockSpec((hidden,), lambda i: (0,)),
                pl.BlockSpec((hidden,), lambda i: (0,)),
            ],
            out_specs=[
                pl.BlockSpec((blk, hidden), lambda i: (i, 0)),
                pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((prows, hidden), x2.dtype),
                jax.ShapeDtypeStruct((prows, 1), jnp.float32),
                jax.ShapeDtypeStruct((prows, 1), jnp.float32),
            ],
            interpret=pallas_interpret(),
            name="ln_fwd",
        )(x2p, w, b)
    return y[:rows], mean[:rows], rstd[:rows]


def _bwd_pallas(g2, x2, mean, rstd, weight, rms):
    rows, hidden = x2.shape
    affine = weight is not None
    blk = tuned_row_block("layer_norm_bwd", rows, hidden)
    g2p, _ = _pad_rows(g2, blk)
    x2p, _ = _pad_rows(x2, blk)
    meanp, _ = _pad_rows(mean, blk)
    rstdp, _ = _pad_rows(rstd, blk)
    prows = x2p.shape[0]
    grid = prows // blk
    w = weight if affine else jnp.zeros((hidden,), x2.dtype)
    kernel = functools.partial(_bwd_kernel, rms=rms, affine=affine)
    with kernel_span("ln_bwd"):
        dx, dwp, dbp = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((blk, hidden), lambda i: (i, 0)),
                pl.BlockSpec((blk, hidden), lambda i: (i, 0)),
                pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                pl.BlockSpec((hidden,), lambda i: (0,)),
            ],
            out_specs=[
                pl.BlockSpec((blk, hidden), lambda i: (i, 0)),
                pl.BlockSpec((1, hidden), lambda i: (0, 0)),
                pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((prows, hidden), x2.dtype),
                jax.ShapeDtypeStruct((1, hidden), jnp.float32),
                jax.ShapeDtypeStruct((1, hidden), jnp.float32),
            ],
            interpret=pallas_interpret(),
            name="ln_bwd",
        )(g2p, x2p, meanp, rstdp, w)
    dw = dwp[0] if affine else None
    db = dbp[0] if affine else None
    return dx[:rows], dw, db


# ----------------------------- custom_vjp plumbing --------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm(x, weight, bias, eps, rms):
    y, _, _ = _norm_fwd_impl(x, weight, bias, eps, rms)
    return y


def _norm_fwd_impl(x, weight, bias, eps, rms):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y2, mean, rstd = _fwd_pallas(x2, weight, bias, eps, rms)
    return y2.reshape(shape), mean, rstd


def _norm_fwd(x, weight, bias, eps, rms):
    y, mean, rstd = _norm_fwd_impl(x, weight, bias, eps, rms)
    return y, (x, weight, bias, mean, rstd)


def _norm_bwd(eps, rms, res, g):
    x, weight, bias, mean, rstd = res
    shape = x.shape
    g2 = g.reshape(-1, shape[-1])
    x2 = x.reshape(-1, shape[-1])
    dx, dw, db = _bwd_pallas(g2, x2, mean, rstd, weight, rms)
    dx = dx.reshape(shape)
    dw = None if weight is None else dw.astype(weight.dtype)
    db = None if bias is None else (db.astype(bias.dtype) if db is not None else None)
    return (dx, dw, db)


_norm.defvjp(_norm_fwd, _norm_bwd)


# --------------------------------- public API -------------------------------

def fused_layer_norm(x, weight=None, bias=None, eps: float = 1e-5,
                     use_pallas_override: Optional[bool] = None):
    """Fused affine/plain LayerNorm ≡ fused_layer_norm_affine /
    fused_layer_norm (apex/normalization/fused_layer_norm.py:168-201)."""
    if use_pallas_fusable(use_pallas_override):
        return _norm(x, weight, bias, eps, False)
    return layer_norm_reference(x, weight, bias, eps)


def fused_rms_norm(x, weight=None, eps: float = 1e-5,
                   use_pallas_override: Optional[bool] = None):
    """Fused RMSNorm ≡ fused_rms_norm_affine / fused_rms_norm
    (apex/normalization/fused_layer_norm.py:189-201)."""
    if use_pallas_fusable(use_pallas_override):
        return _norm(x, weight, None, eps, True)
    return rms_norm_reference(x, weight, eps)


class FusedLayerNorm:
    """Module facade ≡ apex.normalization.FusedLayerNorm
    (apex/normalization/fused_layer_norm.py:204-297)."""

    def __init__(self, normalized_shape, eps=1e-5, elementwise_affine=True):
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        if len(normalized_shape) != 1:
            raise NotImplementedError("only last-dim LayerNorm is supported")
        self.normalized_shape = tuple(normalized_shape)
        self.eps = eps
        self.elementwise_affine = elementwise_affine

    def init(self, key=None, dtype=jnp.float32):
        if not self.elementwise_affine:
            return {}
        h = self.normalized_shape[0]
        return {"weight": jnp.ones((h,), dtype), "bias": jnp.zeros((h,), dtype)}

    def apply(self, params, x, use_pallas_override=None):
        w = params.get("weight") if self.elementwise_affine else None
        b = params.get("bias") if self.elementwise_affine else None
        return fused_layer_norm(x, w, b, self.eps, use_pallas_override)


class FusedRMSNorm:
    """≡ apex.normalization.FusedRMSNorm (fused_layer_norm.py:300-397)."""

    def __init__(self, normalized_shape, eps=1e-5, elementwise_affine=True):
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.eps = eps
        self.elementwise_affine = elementwise_affine

    def init(self, key=None, dtype=jnp.float32):
        if not self.elementwise_affine:
            return {}
        return {"weight": jnp.ones(self.normalized_shape, dtype)}

    def apply(self, params, x, use_pallas_override=None):
        w = params.get("weight") if self.elementwise_affine else None
        return fused_rms_norm(x, w, self.eps, use_pallas_override)
