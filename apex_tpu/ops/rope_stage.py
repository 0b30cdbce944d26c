"""Staging for the head-major flash kernels: a projection's output
becomes the kernel's operand in one pass, rotary embedding included.

Latent attention (models/mla_moe.py) hands `flash_attention` queries
and keys whose heads are `dn` lanes without a position and `dr` lanes
with one.  The projections leave them token-major, `(B, S, heads * d)`;
the kernels read `(B, heads, S, dn + dr)`.  Written with `jax.numpy`
the way from one to the other is a slice, a rotation, a concatenation
and a transpose, and the TPU compiler gives each its own trip through
HBM (it moves S into the lanes for the rotation and back for the
kernel).  `stage_heads` is that way as one Pallas pass: a block of
`rows` tokens of `g` heads is read where the GEMM wrote it, the rotary
lanes are turned in registers, and the block is written head-major.
Its backward, `rope_unstage`, is the same pass run the other way.

The rotary lanes come in *halves*: a head's `dr` lanes are `(x1 | x2)`
with the partners of a pair `dr / 2` lanes apart, so a rotation is
`(x1 c - x2 s | x2 c + x1 s)`.  A caller whose weights pair lanes
`(2i, 2i + 1)` permutes the weight's columns (`halves`): q's and k's
rotary lanes then share an order, which is all a dot product sees.

`rope` is either per head, `(B, S, heads * dr)`, and turned here; or
one row a token that every head shares, `(B, S, dr)`, already turned
(it is a 64-wide row: its rotation is no pass to save): then the pass
writes it once a head and the backward sums its gradient over the
heads, so no `(B, S, heads, dr)` array exists in either direction.  A
model without positions (`models/hybrid_moe.py`'s latent attention)
keeps the two widths and turns nothing: its keys' shared row goes in
as it is, and its queries' per-head lanes with `per_head` and no
tables, the same pass without the rotation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.monitor.compile.startup import kernel_span
from apex_tpu.ops._common import on_chip, pallas_interpret, use_pallas

LANES = 128
ROWS = 512           # tokens a block
HEADS = 4            # heads a block


def rope_tables(seq: int, dim: int, theta: float):
    """(cos, sin), each (seq, dim / 2) fp32: position * theta^(-2i/dim)."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angle), jnp.sin(angle)


def halves(w, dn: int = 0):
    """The columns of w (..., dn + dr) past the first dn from pairs
    (2i, 2i + 1) to halves (i, i + dr / 2)."""
    pairs = w[..., dn:].reshape(*w.shape[:-1], -1, 2)
    turned = jnp.swapaxes(pairs, -1, -2).reshape(*w.shape[:-1], -1)
    return jnp.concatenate([w[..., :dn], turned], axis=-1) if dn else turned


def turn_halves(x, cos, sin):
    """Rotary embedding over the last axis of x (..., S, d) in halves
    order; cos, sin (S, d / 2) or broadcastable to x's halves.  fp32
    inside, x's dtype out."""
    h = x.shape[-1] // 2
    x1 = x[..., :h].astype(jnp.float32)
    x2 = x[..., h:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# --------------------------- reference (jnp) path ---------------------------

def stage_heads_reference(nope, rope, num_heads: int, tables=None,
                          per_head: bool = False):
    b, s, _ = nope.shape
    if tables is None and per_head:
        r = rope.reshape(b, s, num_heads, -1)
    elif tables is None:
        r = jnp.broadcast_to(rope[:, :, None], (b, s, num_heads,
                                                rope.shape[-1]))
    else:
        cos, sin = tables
        r = turn_halves(rope.reshape(b, s, num_heads, -1), cos[:, None],
                        sin[:, None])
    return jnp.concatenate([nope.reshape(b, s, num_heads, -1), r],
                           axis=-1).transpose(0, 2, 1, 3)


# ------------------------------ pallas kernels ------------------------------

def _turn_block(r, cos, sin, dr):
    """r (rows, g * dr) fp32, g heads' rotary lanes side by side; cos
    and the signed sin (-s on a head's first half, +s on its second)
    laid out alike."""
    w, h = r.shape[-1], dr // 2
    lane = lax.broadcasted_iota(jnp.int32, r.shape, 1)
    first = (lane & (dr - 1)) < h
    partner = jnp.where(first, pltpu.roll(r, w - h, 1), pltpu.roll(r, h, 1))
    return r * cos + partner * sin


def _stage_kernel(*refs, g, dn, dr, shared, turn):
    if turn:
        nope_ref, rope_ref, cos_ref, sin_ref, o_ref = refs
        turned = _turn_block(rope_ref[0].astype(jnp.float32), cos_ref[...],
                             sin_ref[...], dr).astype(o_ref.dtype)
    else:
        nope_ref, rope_ref, o_ref = refs
        turned = rope_ref[0]
    for j in range(g):
        o_ref[0, j, :, :dn] = nope_ref[0, :, j * dn:(j + 1) * dn]
        o_ref[0, j, :, dn:] = (turned if shared
                               else turned[:, j * dr:(j + 1) * dr])


def _unstage_kernel(*refs, g, dn, dr, shared, turn):
    if turn:
        g_ref, cos_ref, sin_ref, dnope_ref, drope_ref = refs
    else:
        g_ref, dnope_ref, drope_ref = refs
    for j in range(g):
        dnope_ref[0, :, j * dn:(j + 1) * dn] = g_ref[0, j, :, :dn]
    if not (shared or turn):
        for j in range(g):
            drope_ref[0, :, j * dr:(j + 1) * dr] = g_ref[0, j, :, dn:]
        return
    pieces = [g_ref[0, j, :, dn:].astype(jnp.float32) for j in range(g)]
    if shared:
        # one row a token for every head: the sum over this block's
        # heads, added to what the blocks before it left

        @pl.when(pl.program_id(2) == 0)
        def _():
            drope_ref[...] = jnp.zeros(drope_ref.shape, drope_ref.dtype)
        drope_ref[0] += functools.reduce(jnp.add, pieces)
    else:
        drope_ref[0] = _turn_block(
            jnp.concatenate(pieces, axis=-1), cos_ref[...], sin_ref[...], dr
        ).astype(drope_ref.dtype)


def _blocks(s, nh, dn, dr):
    """(rows, heads) a block, or None where no block the chip takes
    divides the shapes (interpret mode takes any: whole arrays then)."""
    rows = next((r for r in (ROWS, 256, 128) if s % r == 0), None)
    g = next((g for g in (HEADS, 2) if nh % g == 0
              and (g * dr) % LANES == 0), None)
    if rows and g and dn % LANES == 0:
        return rows, g
    return None if on_chip() else (s, nh)


def _lane_tables(cos, sin, g):
    """(S, g * dr) fp32 each: cos and the signed sin of `_turn_block`."""
    return (jnp.tile(jnp.concatenate([cos, cos], axis=-1), (1, g)),
            jnp.tile(jnp.concatenate([-sin, sin], axis=-1), (1, g)))


@functools.lru_cache(maxsize=None)
def _call(backward, b, s, nh, dn, dr, shared, turn, rows, g, dtype,
          interpret):
    grid = (b, s // rows, nh // g)
    flat = lambda w: pl.BlockSpec((1, rows, g * w), lambda i, j, k: (i, j, k))
    heads = pl.BlockSpec((1, g, rows, dn + dr), lambda i, j, k: (i, k, j, 0))
    table = pl.BlockSpec((rows, g * dr), lambda i, j, k: (j, 0))
    row = pl.BlockSpec((1, rows, dr), lambda i, j, k: (i, j, 0))
    tables = [table, table] if turn else []
    params = pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "arbitrary" if backward and shared
        else "parallel"))
    if backward:
        return pl.pallas_call(
            functools.partial(_unstage_kernel, g=g, dn=dn, dr=dr,
                              shared=shared, turn=turn),
            grid=grid, in_specs=[heads] + tables,
            out_specs=[flat(dn), row if shared else flat(dr)],
            out_shape=[
                jax.ShapeDtypeStruct((b, s, nh * dn), dtype),
                jax.ShapeDtypeStruct((b, s, dr), jnp.float32) if shared
                else jax.ShapeDtypeStruct((b, s, nh * dr), dtype)],
            compiler_params=params, interpret=interpret,
            name="rope_unstage")
    return pl.pallas_call(
        functools.partial(_stage_kernel, g=g, dn=dn, dr=dr, shared=shared,
                          turn=turn),
        grid=grid, in_specs=[flat(dn), row if shared else flat(dr)] + tables,
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct((b, nh, s, dn + dr), dtype),
        compiler_params=params, interpret=interpret, name="rope_stage")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _stage(nope, rope, tables, dims, blocks, shared):
    return _stage_fwd(nope, rope, tables, dims, blocks, shared)[0]


def _stage_fwd(nope, rope, tables, dims, blocks, shared):
    b, s, _ = nope.shape
    call = _call(False, b, s, *dims, shared, tables is not None, *blocks,
                 jnp.dtype(nope.dtype), pallas_interpret())
    lanes = () if tables is None else _lane_tables(*tables, blocks[1])
    with kernel_span("rope_stage"):
        return call(nope, rope, *lanes), tables


def _stage_bwd(dims, blocks, shared, tables, grad):
    b, _, s, _ = grad.shape
    call = _call(True, b, s, *dims, shared, tables is not None, *blocks,
                 jnp.dtype(grad.dtype), pallas_interpret())
    if tables is None:
        with kernel_span("rope_unstage"):
            d_nope, d_rope = call(grad)
        return d_nope, d_rope.astype(grad.dtype), None
    cos, sin = tables
    # the transpose of a rotation is the rotation by the opposite angle
    with kernel_span("rope_unstage"):
        d_nope, d_rope = call(grad, *_lane_tables(cos, -sin, blocks[1]))
    return d_nope, d_rope, (jnp.zeros_like(cos), jnp.zeros_like(sin))


_stage.defvjp(_stage_fwd, _stage_bwd)


def stage_heads(nope, rope, num_heads: int, tables=None, *,
                per_head: bool = False, use_pallas_override=None):
    """The flash kernels' operand `(B, heads, S, dn + dr)` from a
    projection's output `nope` (B, S, heads * dn) and `rope`: with
    `tables` = (cos, sin), each (S, dr / 2), `rope` is (B, S, heads *
    dr) in halves order and is turned here; without, it is (B, S, dr),
    one row a token that every head gets as it is, or with `per_head`
    (B, S, heads * dr), a head's own lanes as they are.  Differentiable
    in `nope` and `rope`."""
    b, s, width = nope.shape
    assert width % num_heads == 0
    per_head = per_head or tables is not None
    dr = rope.shape[-1] // (num_heads if per_head else 1)
    assert tables is None or tables[0].shape == (s, dr // 2)
    dims = (num_heads, width // num_heads, dr)
    blocks = _blocks(s, *dims) if use_pallas(use_pallas_override) else None
    if blocks is None:
        return stage_heads_reference(nope, rope, num_heads, tables, per_head)
    return _stage(nope, rope, tables, dims, blocks, not per_head)
