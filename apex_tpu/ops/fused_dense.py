"""Fused dense (matmul + bias + activation) kernels.

≡ the reference's `fused_dense_cuda` extension (csrc/fused_dense.cpp:188-191,
cublasLt epilogue kernels csrc/fused_dense_cuda.cu) and its wrappers
apex.fused_dense.{FusedDense,FusedDenseGeluDense}
(apex/fused_dense/fused_dense.py:7-99), plus
`fused_weight_gradient_mlp_cuda` (csrc/megatron/fused_weight_gradient_dense.cpp:19-20)
— the wgrad GEMM that accumulates directly into a persistent fp32
main_grad buffer.

TPU design: a Pallas MXU matmul kernel with the bias+activation epilogue
fused into the final K-step (≡ cublasLt epilogues), fp32 accumulation
scratch, custom_vjp whose backward runs plain XLA matmuls (dgrad/wgrad
are bare GEMMs — XLA is already optimal there).  Off-TPU (and under
`use_pallas=False`) the forward is a jnp chain that XLA fuses to the
same schedule.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.monitor.compile.startup import kernel_span
from apex_tpu.ops._common import pallas_interpret, round_up, use_pallas


def _act(y, activation):
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "gelu":
        return jax.nn.gelu(y, approximate=True)
    if activation == "sigmoid":
        return jax.nn.sigmoid(y)
    if activation in (None, "none"):
        return y
    raise ValueError(f"unknown activation {activation!r}")


# --------------------------- reference (jnp) path ---------------------------

def linear_bias_reference(x, w, b=None, activation=None):
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return _act(y, activation).astype(x.dtype)


# ------------------------------ pallas kernel -------------------------------

def _matmul_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, activation,
                   has_bias, k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        y = acc_ref[...]
        if has_bias:
            y = y + b_ref[0, :].astype(jnp.float32)
        o_ref[...] = _act(y, activation).astype(o_ref.dtype)


def _matmul_pallas(x2, w, b, activation, bm=256, bn=256, bk=512):
    m, kdim = x2.shape
    _, n = w.shape
    bm = min(bm, round_up(m, 8))
    bn = min(bn, round_up(n, 128))
    bk = min(bk, round_up(kdim, 128))
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(kdim, bk)
    xp = jnp.pad(x2, ((0, mp - m), (0, kp - kdim))) if (mp, kp) != (m, kdim) else x2
    wp = jnp.pad(w, ((0, kp - kdim), (0, np_ - n))) if (kp, np_) != (kdim, n) else w
    has_bias = b is not None
    # bias rides as a (1, n) row: TPU Mosaic requires >=2-D blocks with a
    # 128-lane minor dim (a 1-D spec compiles in interpret mode only)
    bp = jnp.pad(b, (0, np_ - n)) if has_bias and np_ != n else (
        b if has_bias else jnp.zeros((np_,), x2.dtype))
    bp = bp.reshape(1, np_)
    k_steps = kp // bk
    with kernel_span("fused_dense"):
        out = pl.pallas_call(
            functools.partial(_matmul_kernel, activation=activation,
                              has_bias=has_bias, k_steps=k_steps),
            grid=(mp // bm, np_ // bn, k_steps),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), x2.dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=pallas_interpret(),
            name="fused_dense",
        )(xp, wp, bp)
    return out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_linear(x2, w, b, activation):
    return _matmul_pallas(x2, w, b, activation)


def _fused_linear_fwd(x2, w, b, activation):
    # save pre-activation only when the activation needs it
    if activation in (None, "none"):
        y = _matmul_pallas(x2, w, b, activation)
        return y, (x2, w, b, None)
    pre = _matmul_pallas(x2, w, b, None)
    return _act(pre.astype(jnp.float32), activation).astype(x2.dtype), (
        x2, w, b, pre)


def _fused_linear_bwd(activation, res, g):
    x2, w, b, pre = res
    g32 = g.astype(jnp.float32)
    if activation == "relu":
        g32 = jnp.where(pre > 0, g32, 0.0)
    elif activation == "gelu":
        _, vjp = jax.vjp(lambda p: jax.nn.gelu(p.astype(jnp.float32),
                                               approximate=True), pre)
        (g32,) = vjp(g32)
    elif activation == "sigmoid":
        s = jax.nn.sigmoid(pre.astype(jnp.float32))
        g32 = g32 * s * (1.0 - s)
    g_cast = g32.astype(x2.dtype)
    dx = jnp.dot(g_cast, w.T, preferred_element_type=jnp.float32).astype(x2.dtype)
    dw = jnp.dot(x2.T, g_cast, preferred_element_type=jnp.float32).astype(w.dtype)
    db = None if b is None else jnp.sum(g32, axis=0).astype(b.dtype)
    return dx, dw, db


_fused_linear.defvjp(_fused_linear_fwd, _fused_linear_bwd)


# --------------------------------- public API -------------------------------

def linear_bias(x, w, b=None, activation: Optional[str] = None,
                use_pallas_override: Optional[bool] = None):
    """y = act(x @ w + b) with the epilogue fused.

    ≡ fused_dense_cuda.linear_bias_forward (csrc/fused_dense.cpp:188).
    x: (..., K), w: (K, N), b: (N,).
    """
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if use_pallas(use_pallas_override):
        y = _fused_linear(x2, w, b, activation)
    else:
        y = linear_bias_reference(x2, w, b, activation)
    return y.reshape(shape[:-1] + (w.shape[-1],))


def linear_gelu_linear(x, w1, b1, w2, b2,
                       use_pallas_override: Optional[bool] = None):
    """y = (gelu(x@w1+b1))@w2+b2 ≡ fused_dense_cuda.linear_gelu_linear_forward
    (csrc/fused_dense.cpp:190)."""
    h = linear_bias(x, w1, b1, "gelu", use_pallas_override)
    return linear_bias(h, w2, b2, None, use_pallas_override)


def qkv_split_heads(qkv, num_heads, head_dim):
    """Packed-QKV head split: (S, B, 3·nh·d) → three (B, nh, S, d).

    The QKV projection is already ONE GEMM (a single (H, 3H)
    ColumnParallelLinear ≡ the reference's fused QKV,
    standalone_transformer_lm.py attention).  What the round-6 per-GEMM
    roofline flagged was the glue AFTER it: slicing q/k/v out of the
    middle of the packed reshape and transposing each slice separately
    costs three strided (S, B, nh, d) copies.  This helper transposes
    the PACKED tensor once — (3, B, nh, S, d), one fused relayout whose
    minor dim stays the lane-aligned head_dim — and hands out q/k/v as
    leading-dim views (no further copy).  Gradient is the mirrored
    single transpose (AD of transpose+concat).
    """
    s, b = qkv.shape[:2]
    qkv = qkv.reshape(s, b, 3, num_heads, head_dim)
    qkv = qkv.transpose(2, 1, 3, 0, 4)  # (3, B, nh, S, d)
    return qkv[0], qkv[1], qkv[2]


def wgrad_accum(main_grad, x, g):
    """main_grad += x^T @ g with fp32 accumulation.

    ≡ fused_weight_gradient_mlp_cuda.wgrad_gemm_accum_fp32
    (csrc/megatron/fused_weight_gradient_dense.cpp:19) — the Megatron
    linear's weight-grad GEMM that accumulates into a persistent fp32
    buffer.  Under jit with donation the accumulate is in-place.
    """
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    return main_grad + jnp.dot(x2.T, g2, preferred_element_type=jnp.float32)


class FusedDense:
    """≡ apex.fused_dense.FusedDense (apex/fused_dense/fused_dense.py:64)."""

    def __init__(self, in_features, out_features, bias=True):
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = bias

    def init(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        bound = 1.0 / jnp.sqrt(self.in_features)
        p = {"weight": jax.random.uniform(
            k1, (self.in_features, self.out_features), dtype, -bound, bound)}
        if self.use_bias:
            p["bias"] = jax.random.uniform(k2, (self.out_features,), dtype,
                                           -bound, bound)
        return p

    def apply(self, params, x, use_pallas_override=None):
        return linear_bias(x, params["weight"], params.get("bias"),
                           None, use_pallas_override)


class FusedDenseGeluDense:
    """≡ apex.fused_dense.FusedDenseGeluDense (fused_dense.py:82)."""

    def __init__(self, in_features, intermediate_features, out_features,
                 bias=True):
        self.sizes = (in_features, intermediate_features, out_features)
        self.use_bias = bias

    def init(self, key, dtype=jnp.float32):
        i, h, o = self.sizes
        k1, k2, k3, k4 = jax.random.split(key, 4)
        b1 = 1.0 / jnp.sqrt(i)
        b2 = 1.0 / jnp.sqrt(h)
        return {
            "weight1": jax.random.uniform(k1, (i, h), dtype, -b1, b1),
            "bias1": jax.random.uniform(k2, (h,), dtype, -b1, b1),
            "weight2": jax.random.uniform(k3, (h, o), dtype, -b2, b2),
            "bias2": jax.random.uniform(k4, (o,), dtype, -b2, b2),
        }

    def apply(self, params, x, use_pallas_override=None):
        return linear_gelu_linear(x, params["weight1"], params["bias1"],
                                  params["weight2"], params["bias2"],
                                  use_pallas_override)
