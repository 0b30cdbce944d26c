"""Per-channel batch statistics kernel — the SyncBatchNorm compute core.

≡ the reference's `syncbn` extension (csrc/syncbn.cpp:99-108, Welford
kernels csrc/welford.cu:259-702).  The CUDA design computes local
Welford mean/var, all-gathers (mean, var, count) and merges with
welford_parallel; the TPU design computes local per-channel (sum, sumsq,
count) in one Pallas pass — fp32 accumulation makes plain moments as
stable as Welford at BN's scale — and merges across the process group
with a single `lax.psum` (see parallel/sync_batchnorm.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.monitor.compile.startup import kernel_span
from apex_tpu.ops._common import (
    pallas_interpret,
    row_block,
    use_pallas_fusable,
)


def _stats_kernel(x_ref, sum_ref, sq_ref):
    x = x_ref[...].astype(jnp.float32)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    sum_ref[...] += jnp.sum(x, axis=0, keepdims=True)
    sq_ref[...] += jnp.sum(x * x, axis=0, keepdims=True)


@jax.custom_vjp
def channel_sums(x2):
    """(sum, sumsq) over rows of a (rows, C) array, fp32."""
    return _channel_sums_impl(x2)


def _channel_sums_impl(x2):
    # fusable-op rule (≡ LayerNorm, ops/_common.use_pallas_fusable):
    # XLA fuses the (sum, sumsq) multi-output reduction into the
    # producing conv's consumer; the standalone Pallas kernel costs a
    # custom-call boundary + an extra HBM pass.  Measured on v5e at
    # the RN50 bench point (b256): BN stack fwd+bwd 55.1 ms (Pallas)
    # vs 21.6 ms (XLA), full model fwd(train) 66.4 -> 27.6 ms
    # (scripts/resnet_profile.py) — the 4-round ResNet plateau was
    # mostly THIS kernel.
    if not use_pallas_fusable(None):
        x32 = x2.astype(jnp.float32)
        return jnp.sum(x32, axis=0), jnp.sum(x32 * x32, axis=0)
    rows, c = x2.shape
    blk = row_block(rows, c)
    pad = (-rows) % blk
    xp = jnp.pad(x2, ((0, pad), (0, 0))) if pad else x2
    with kernel_span("welford"):
        s, q = pl.pallas_call(
            _stats_kernel,
            grid=(xp.shape[0] // blk,),
            in_specs=[pl.BlockSpec((blk, c), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0)),
                       pl.BlockSpec((1, c), lambda i: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                       jax.ShapeDtypeStruct((1, c), jnp.float32)],
            interpret=pallas_interpret(),
            name="welford",
        )(xp)
    return s[0], q[0]


def _channel_sums_fwd(x2):
    return _channel_sums_impl(x2), x2


def _channel_sums_bwd(x2, g):
    ds, dq = g
    dx = jnp.broadcast_to(ds, x2.shape) + 2.0 * x2.astype(jnp.float32) * dq
    return (dx.astype(x2.dtype),)


channel_sums.defvjp(_channel_sums_fwd, _channel_sums_bwd)


def batch_stats(x, reduce_axes):
    """Per-channel (mean, var, count) reducing over `reduce_axes`.

    ≡ syncbn.welford_mean_var (csrc/welford.cu:259).  Channel dim = the
    one axis not in reduce_axes.
    """
    ndim = x.ndim
    reduce_axes = tuple(a % ndim for a in reduce_axes)
    (chan,) = [a for a in range(ndim) if a not in reduce_axes]
    perm = list(reduce_axes) + [chan]
    x2 = jnp.transpose(x, perm).reshape(-1, x.shape[chan])
    count = x2.shape[0]
    s, q = channel_sums(x2)
    mean = s / count
    var = jnp.maximum(q / count - mean * mean, 0.0)
    return mean, var, count


def merge_stats(mean, var, count, axis_name):
    """Merge per-device (mean, var, count) over a mesh axis.

    ≡ the all_gather + syncbn.welford_parallel merge
    (apex/parallel/optimized_sync_batchnorm_kernel.py:36-43,
    csrc/welford.cu:569) — here one psum of (count, count*mean,
    count*(var+mean²)) using the parallel-variance identity.
    """
    n = jnp.asarray(count, jnp.float32)
    tn = jax.lax.psum(n, axis_name)
    tmean = jax.lax.psum(n * mean, axis_name) / tn
    tsq = jax.lax.psum(n * (var + mean * mean), axis_name) / tn
    return tmean, jnp.maximum(tsq - tmean * tmean, 0.0), tn
