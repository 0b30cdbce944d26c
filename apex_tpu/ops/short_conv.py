"""The gated short convolution of a convolutional token mixer (LFM2's
`conv` layers): between the mixer's two projections, token-major in and
out, nothing but gates and taps.

    [B | C | u] = bcu            three H-wide thirds, in this order
    z           = B * u
    c_t         = sum_j w[:, j] * z_(t - taps + 1 + j)     z = 0 before the row
    out         = C * c

a causal depthwise convolution over time with one weight a channel and
tap, `w[:, taps - 1]` on the current token (PyTorch's `Conv1d(H, H,
taps, groups=H, padding=taps - 1)` cut to the first S outputs), between
an input gate and an output gate.  No activation, no bias, and no state
but the `taps - 1` previous `z`.

Compiled `jax.numpy` with its plain pullback: nothing here changes a
layout, so the compiler is free to fuse the gates into what stands
beside them.  On the v5e at (1, 8192, 3 x 2048) in bf16 the forward and
backward together take 3.9 ms a layer where their bytes need 0.45 (PR
39's chip run); the same body under `jax.checkpoint`, which keeps `bcu`
and `w` alone, took 4.3 ms and 218 MB a layer less.  A Pallas pass that
shifts `z` in registers is what the reading asks for (`ops/
conv_stage.py` is the pattern).
"""

from __future__ import annotations

import jax.numpy as jnp


def gated_short_conv(bcu, w):
    """bcu (B, S, 3H), the input projection's output; w (H, taps), a
    channel's taps, oldest first -> (B, S, H) in bcu's dtype, float32
    inside.  Differentiable in both."""
    if bcu.shape[-1] != 3 * w.shape[0]:
        raise ValueError(f"bcu {bcu.shape} is not three thirds of the "
                         f"taps' {w.shape[0]} channels")
    f32 = jnp.float32
    taps, s = w.shape[1], bcu.shape[1]
    b, c, u = jnp.split(bcu.astype(f32), 3, axis=-1)
    z = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(f32)
    conv = sum(z[:, j:j + s] * w[:, j] for j in range(taps))
    return (c * conv).astype(bcu.dtype)
