"""Staging for the delta rule's scan: a projection's output becomes the
scan's head-major operand in one pass, the short convolution included.

Kimi Delta Attention (models/hybrid_moe.py) and Gated DeltaNet
(models/olmo_hybrid.py) hand `gated_delta_rule` q, k and v that are
each `SiLU(conv(a W))`: a causal depthwise convolution over time,
`taps` weights a channel, a tap dropped where the token it reads is of
another document of a packed row; q's and k's heads scaled to unit
length besides, q by `d ** -0.5` more.  The GEMM leaves a stream
token-major, `(B, S, heads * d)`; the scan reads `(B, heads, S, d)`.
Written with `jax.numpy` the way from one to the other is a pad,
`taps` shifted float32 slices, their masks, a sum, SiLU, a reduction
over a head's lanes, a scaling and a transpose, and the TPU compiler
gives them several trips through HBM, 6-10 times the bytes' time.
`stage_conv_heads` is that way as one Pallas pass for all of a layer's
streams, each at its own head width (KDA's three at 128, Gated
DeltaNet's q and k at 96 and v at 192): a block of `rows` tokens of
`g` heads is read where the GEMM wrote it, with the sublane tile of
rows before it for the taps that reach back, everything between is
float32 in registers, and the block is written head-major, a head's
lanes at a time.  A block's `g` heads fill whole lane tiles of every
stream (four 128-wide heads), or, where no divisor of the heads does
(30 heads of 96), are all of them: a block may always be as wide as
the array.  Its backward, `conv_unstage`, reads the cotangent
head-major and x, recomputes the sum, and writes dx token-major and
float32 partial sums of dw (eight rows a block of lanes, which a small
compiled reduction finishes); it keeps x, w and the document ids,
nothing float32 and nothing head-major.

The pullback of a tap needs the pre-activation's gradient of the
`taps - 1` tokens *after* a token.  The backward walks a row's blocks
from its end, and the gradient of a block's first rows waits in VMEM
for the block before it (on the chip that carry beat a second halo,
recomputed from the rows behind the block, by 0.12 ms a layer).  A
grid step computes a head's whole block at a time: cut into
sub-blocks under a loop, every pass pays the chain from load to store
again (32 rows a pass took three times as long); past 256 rows a
block nothing more is won.

Rounding: the taps' sum is rounded to the stream's dtype where the
`jax.numpy` body rounds it; SiLU and the unit scaling are float32 and
the result is rounded once, where the body rounds SiLU's output in
between.

Which path a call takes is read from its input: the kernels where
`use_pallas` says so, S is a multiple of the dtype's sublane tile and
every head's width fills its lane tiles (`_common.fills_lane_tiles`:
96, 192, a multiple of 128); `stage_conv_heads_reference`,
the `jax.numpy` body, anywhere else, and off the chip.  `stats()`
counts both while tracing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.monitor.compile.startup import kernel_span
from apex_tpu.ops._common import (
    LANES,
    fills_lane_tiles,
    pallas_interpret,
    use_pallas,
)

ROWS = 256           # tokens a block
HEADS = 4            # heads a block
# the most a stream's float32 block may hold: 256 rows of four
# 128-wide heads hold an eighth of it, 128 rows of thirty 192-wide heads
# most of it (on the chip 128 rows there took 5.43 ms a layer forward
# and backward, 64 rows 6.67, 32 rows 9.13)
BLOCK_BYTES = 4 * 2 ** 20
EPS = 1e-12          # under the root of a head's squared length

# calls traced since the last reset, and those that took the kernels
_calls = {"calls": 0, "kernel_calls": 0}


def stats():
    """{"calls": `stage_conv_heads` calls traced since the last reset
    (a call that was differentiated counts once), "kernel_calls": those
    of them that took the Pallas pair}."""
    return dict(_calls)


def reset_stats():
    for key in _calls:
        _calls[key] = 0


# --------------------------- reference (jnp) path ---------------------------

def tap_masks(ids, taps: int):
    """`masks[r - 1]` (B, S, 1) float32: 1 where the token r back is of
    the same document of `ids` (B, S), 0 where it is not or lies before
    the row."""
    def back(r):
        return jnp.pad(ids[:, :-r], ((0, 0), (r, 0)), constant_values=-1)
    return tuple((back(r) == ids)[..., None].astype(jnp.float32)
                 for r in range(1, taps))


def _conv_reference(x, w, masks=None):
    """SiLU of the causal depthwise convolution over time: x (B, S,
    C), w (taps, C); tap j weighs the token taps - 1 - j back, or 0
    where that token is of the document before."""
    taps = w.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))

    def tap(j):
        term = padded[:, j:j + s].astype(jnp.float32) \
            * w[j].astype(jnp.float32)
        back = taps - 1 - j
        if masks is None or not back:
            return term
        return term * masks[back - 1]

    y = sum(tap(j) for j in range(taps))
    # rounded where the backward keeps it: the sum, not its terms
    return jax.nn.silu(y.astype(x.dtype))


def stage_conv_heads_reference(xs, ws, num_heads: int, scales, ids=None,
                               masks=None):
    """`stage_conv_heads` in `jax.numpy`; `masks` are `tap_masks(ids)`
    where the caller has them already."""
    if masks is None and ids is not None:
        masks = tap_masks(ids, ws[0].shape[0])
    f32 = jnp.float32
    outs = []
    for x, w, scale in zip(xs, ws, scales, strict=True):
        b, s, width = x.shape
        y = _conv_reference(x, w, masks).reshape(b, s, num_heads,
                                                 width // num_heads)
        if scale is not None:
            y = y.astype(f32)
            inv = lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + EPS)
            y = (y * (scale * inv)).astype(x.dtype)
        outs.append(y.transpose(0, 2, 1, 3))
    return tuple(outs)


# ------------------------------ pallas kernels ------------------------------
#
# A grid step's blocks, a stream: `x` (1, rows, g * d) of (B, S, n * d)
# and `before`, the `halo` rows of the same array in front of it (the
# first block's are the row's own first and count as zeros); the taps
# (taps, g * d) float32; head-major (1, g, rows, d) of (B, n, S, d).
# With documents the ids come as a column, (1, rows, 1) of (B, S, 1),
# with the halo before and, backward, the halo after; `mask_ref[r - 1]`
# is 1 where the token r back is of the same document, a row a token
# of the block (and, backward, of the halo after it), a lane tile wide.

def _halo(dtype) -> int:
    """Rows of the block before a block that a grid step reads: the
    dtype's sublane tile, the least a block may have."""
    return 32 // jnp.dtype(dtype).itemsize


def _fill_masks(mask_ref, ids_refs, first, last, halo):
    wide = lambda ref: jnp.broadcast_to(ref[0], (ref.shape[1], LANES))
    parts = [jnp.where(first, -1, wide(ids_refs[0])), wide(ids_refs[1])]
    if len(ids_refs) == 3:
        parts.append(jnp.where(last, -2, wide(ids_refs[2])))
    ext = jnp.concatenate(parts, axis=0)
    for r in range(mask_ref.shape[0]):
        mask_ref[r] = (pltpu.roll(ext, r + 1, 0)[halo:] == ext[halo:]
                       ).astype(jnp.float32)


def _masks(mask_ref, width):
    """The taps' masks over a block's lanes, by r - 1."""
    tiles = -(-width // LANES)
    return [jnp.concatenate([mask_ref[r]] * tiles, axis=-1)[:, :width]
            for r in range(mask_ref.shape[0])]


def _masks_by_width(mask_ref, g, ds):
    """`_masks` of every stream's width, each built once."""
    built = {}
    for d in ds:
        if g * d not in built:
            built[g * d] = _masks(mask_ref, g * d)
    return [built[g * d] for d in ds]


# A stream's block is computed at its whole width, g heads side by
# side, but for the sums over a head's own lanes: a traced operation
# costs the same set-up time however wide it is (a head at a time, a
# step's trace and lowering took 9.4 s longer on the chip's host).

def _conv_silu(before_ref, x_ref, first, w_ref, masks):
    """(x as tap r reads it, by r; the rounded sum y; sigmoid(y)), each
    (rows, g * d) float32.  masks: None or by r - 1, the block's rows
    first."""
    f32 = jnp.float32
    before, x = before_ref[0].astype(f32), x_ref[0].astype(f32)
    halo, rows, taps = before.shape[0], x.shape[0], w_ref.shape[0]
    ext = jnp.concatenate([jnp.where(first, 0.0, before), x], axis=0)
    shifted = [x] + [pltpu.roll(ext, r, 0)[halo:] for r in range(1, taps)]
    y = None
    for j in range(taps):
        back = taps - 1 - j
        term = shifted[back] * w_ref[j:j + 1, :]
        if masks is not None and back:
            term = term * masks[back - 1][:rows]
        y = term if y is None else y + term
    y = y.astype(x_ref.dtype).astype(f32)
    return shifted, y, 1.0 / (1.0 + jnp.exp(-y))


def _stage_kernel(*refs, scales, g, ds, docs):
    n = len(scales)
    refs = list(refs)
    ids = [refs.pop(0) for _ in range(2)] if docs else None
    ins, outs, scratch = refs[:3 * n], refs[3 * n:4 * n], refs[4 * n:]
    dtype = outs[0].dtype
    first = pl.program_id(1) == 0
    masks = [None] * n
    if docs:
        _fill_masks(scratch[0], ids, first, None, _halo(dtype))
        masks = _masks_by_width(scratch[0], g, ds)
    for s, (scale, d) in enumerate(zip(scales, ds)):
        _, y, sig = _conv_silu(*ins[3 * s:3 * s + 2], first, ins[3 * s + 2],
                               masks[s])
        act = y * sig
        for h in range(g):
            a = act[:, h * d:(h + 1) * d]
            if scale is not None:
                a = a * (scale * lax.rsqrt(
                    jnp.sum(a * a, axis=-1, keepdims=True) + EPS))
            outs[s][0, h] = a.astype(dtype)


def _fold(x):
    """(rows, width) -> (8, width): the sum of x's sublane tiles."""
    return x.reshape(x.shape[0] // 8, 8, x.shape[1]).sum(axis=0)


def _unstage_kernel(*refs, scales, g, ds, docs):
    n = len(scales)
    refs = list(refs)
    ids = [refs.pop(0) for _ in range(3)] if docs else None
    ins, outs, scratch = refs[:4 * n], refs[4 * n:6 * n], refs[6 * n:]
    carry_refs = scratch[:n]       # a stream's, its own width
    rows, dtype = ins[0].shape[2], outs[0].dtype
    halo = _halo(dtype)
    # the grid's last axis walks a row's blocks from its end
    step, steps = pl.program_id(2), pl.num_programs(2)
    first, last = step == steps - 1, step == 0
    masks = [None] * n
    if docs:
        _fill_masks(scratch[n], ids, first, last, halo)
        masks = _masks_by_width(scratch[n], g, ds)

    @pl.when(last)
    def _():
        for carry_ref in carry_refs:
            carry_ref[...] = jnp.zeros(carry_ref.shape, carry_ref.dtype)
        for dw_ref in outs[1::2]:
            dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    for s, (scale, d) in enumerate(zip(scales, ds)):
        do_ref, before_ref, x_ref, w_ref = ins[4 * s:4 * s + 4]
        dx_ref, dw_ref = outs[2 * s:2 * s + 2]
        carry_ref = carry_refs[s]
        taps = w_ref.shape[0]
        shifted, y, sig = _conv_silu(before_ref, x_ref, first, w_ref,
                                     masks[s])
        grads = [do_ref[0, h].astype(jnp.float32) for h in range(g)]
        if scale is not None:
            act = y * sig
            for h in range(g):
                a = act[:, h * d:(h + 1) * d]
                inv = lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + EPS)
                along = jnp.sum(grads[h] * a, axis=-1, keepdims=True)
                grads[h] = (scale * inv) * (grads[h] - a * (inv * inv * along))
        dy = jnp.concatenate(grads, axis=-1) \
            * (sig * (1.0 + y * (1.0 - sig)))
        # with the pre-activation's gradient of the halo rows behind
        # the block, which the step before this one left
        behind = jnp.concatenate([dy, carry_ref[...]], axis=0)
        carry_ref[...] = dy[:halo]
        dx = dy * w_ref[taps - 1:taps, :]
        dw_ref[0, taps - 1] += _fold(dy * shifted[0])
        for r in range(1, taps):
            # what token t + r passes the token r before it
            passed = behind * masks[s][r - 1] if docs else behind
            dx = dx + pltpu.roll(passed, rows + halo - r, 0)[:rows] \
                * w_ref[taps - 1 - r:taps - r, :]
            dw_ref[0, taps - 1 - r] += _fold(passed[:rows] * shifted[r])
        dx_ref[0] = dx.astype(dtype)


def _blocks(s, nh, ds, taps, dtype):
    """(rows, heads) a block, or None where the kernels do not take
    the shapes: a head's width that leaves more than a quarter of its
    lane tiles empty, a row that is no multiple of the dtype's sublane tile,
    or taps that reach further back than one such tile.  A block's
    heads span whole lane tiles of every stream, or are all the row's
    heads (a block may always be the array's whole width); its rows
    keep the widest stream's float32 block within BLOCK_BYTES."""
    halo = _halo(dtype)
    if (s % halo or not 1 < taps <= halo + 1
            or not all(map(fills_lane_tiles, ds))):
        return None
    g = next((g for g in (HEADS, 2, 1) if nh % g == 0
              and all(g * d % LANES == 0 for d in ds)), nh)
    rows = next(r for r in (ROWS, 128, 64, 32, 16, 8)
                if s % r == 0 and r % halo == 0
                and (r == halo or 4 * r * g * max(ds) <= BLOCK_BYTES))
    return rows, g


@functools.lru_cache(maxsize=None)
def _call(backward, b, s, nh, ds, taps, scales, docs, rows, g, dtype,
          interpret):
    halo, f32 = _halo(dtype), jnp.float32
    per, n_blocks, n = rows // halo, s // rows, len(scales)
    # a block's place along the row from the grid's (batch, blocks,
    # heads), or backward (batch, heads, blocks from the end)
    where = (lambda i, k, j: (i, n_blocks - 1 - j, k)) if backward \
        else (lambda i, j, k: (i, j, k))

    def spec(shape, place):
        return pl.BlockSpec(shape, lambda *at: place(*where(*at)))

    flat = [spec((1, rows, g * d), lambda i, j, k: (i, j, k)) for d in ds]
    before = [spec((1, halo, g * d),
                   lambda i, j, k: (i, jnp.maximum(j * per - 1, 0), k))
              for d in ds]
    heads = [spec((1, g, rows, d), lambda i, j, k: (i, k, j, 0)) for d in ds]
    w = [spec((taps, g * d), lambda i, j, k: (0, k)) for d in ds]
    ids = [spec((1, halo, 1),
                lambda i, j, k: (i, jnp.maximum(j * per - 1, 0), 0)),
           spec((1, rows, 1), lambda i, j, k: (i, j, 0)),
           spec((1, halo, 1), lambda i, j, k: (
               i, jnp.minimum((j + 1) * per, s // halo - 1), 0))]
    kind = dict(scales=scales, g=g, ds=ds, docs=docs)
    limit = 64 * 2 ** 20
    if backward:
        dw = [spec((1, taps, 8, g * d), lambda i, j, k: (i, 0, 0, k))
              for d in ds]
        return pl.pallas_call(
            functools.partial(_unstage_kernel, **kind),
            grid=(b, nh // g, n_blocks),
            in_specs=(ids if docs else []) + [
                x for z in zip(heads, before, flat, w) for x in z],
            out_specs=[x for z in zip(flat, dw) for x in z],
            out_shape=[x for d in ds for x in (
                jax.ShapeDtypeStruct((b, s, nh * d), dtype),
                jax.ShapeDtypeStruct((b, taps, 8, nh * d), f32))],
            scratch_shapes=[pltpu.VMEM((halo, g * d), f32) for d in ds] + (
                [pltpu.VMEM((taps - 1, rows + halo, LANES), f32)]
                if docs else []),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=limit),
            interpret=interpret, name="conv_unstage")
    return pl.pallas_call(
        functools.partial(_stage_kernel, **kind),
        grid=(b, n_blocks, nh // g),
        in_specs=(ids[:2] if docs else []) + [
            x for z in zip(before, flat, w) for x in z],
        out_specs=heads,
        out_shape=[jax.ShapeDtypeStruct((b, nh, s, d), dtype) for d in ds],
        scratch_shapes=[pltpu.VMEM((taps - 1, rows, LANES), f32)]
        if docs else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3, vmem_limit_bytes=limit),
        interpret=interpret, name="conv_stage")


def _call_for(backward, xs, ws, ids, how):
    num_heads, scales, rows, g = how
    b, s, _ = xs[0].shape
    return _call(backward, b, s, num_heads, _widths(xs, num_heads),
                 ws[0].shape[0], scales, ids is not None, rows, g,
                 jnp.dtype(xs[0].dtype), pallas_interpret())


def _widths(xs, num_heads):
    """A head's width in each stream."""
    return tuple(x.shape[-1] // num_heads for x in xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _stage(xs, ws, ids, how):
    return _stage_fwd(xs, ws, ids, how)[0]


def _stage_fwd(xs, ws, ids, how):
    args = [] if ids is None else [ids[..., None]] * 2
    for x, w in zip(xs, ws):
        args += [x, x, w.astype(jnp.float32)]
    with kernel_span("conv_stage"):
        outs = _call_for(False, xs, ws, ids, how)(*args)
    return tuple(outs), (xs, ws, ids)


def _stage_bwd(how, res, grads):
    xs, ws, ids = res
    args = [] if ids is None else [ids[..., None]] * 3
    for x, w, grad in zip(xs, ws, grads):
        args += [grad, x, x, w.astype(jnp.float32)]
    with kernel_span("conv_unstage"):
        outs = _call_for(True, xs, ws, ids, how)(*args)
    # a batch row's and a sublane's partial sums of dw
    dws = tuple(part.sum(axis=(0, 2)).astype(w.dtype)
                for part, w in zip(outs[1::2], ws))
    return tuple(outs[0::2]), dws, None


_stage.defvjp(_stage_fwd, _stage_bwd)


def stage_conv_heads(xs, ws, num_heads: int, scales, ids=None, masks=None,
                     *, use_pallas_override=None):
    """The scan's head-major operands `(B, heads, S, d)`, one a stream,
    from projections' outputs `xs`, each (B, S, heads * d) with a d of
    its own (q and k 96, v 192 in Gated DeltaNet), all of one dtype:
    `SiLU` of the causal depthwise convolution of a stream with its
    `ws` (taps, heads * d), tap j weighing the token
    taps - 1 - j back; then, where the stream's `scales` entry is a
    number and not None, a head's d lanes scaled to unit length times
    that number.  `ids` (B, S) int32: a token's document of a packed
    row; a tap that would read another document's token reads 0.
    `masks`: `tap_masks(ids)` where the caller holds them, which the
    `jax.numpy` body then reads; the kernels read `ids`.
    Differentiable in `xs` and `ws`."""
    xs, ws, scales = tuple(xs), tuple(ws), tuple(scales)
    b, s, _ = xs[0].shape
    taps = ws[0].shape[0]
    if not len(xs) == len(ws) == len(scales) \
            or any(x.shape[:2] != (b, s) or x.ndim != 3
                   or x.shape[-1] % num_heads or x.dtype != xs[0].dtype
                   for x in xs) \
            or any(w.shape != (taps, x.shape[-1]) for x, w in zip(xs, ws)) \
            or (ids is not None and ids.shape != (b, s)):
        raise ValueError(
            f"streams {[x.shape for x in xs]}, taps "
            f"{[w.shape for w in ws]}, scales {scales}, ids "
            f"{None if ids is None else ids.shape}: not as many (B, S, "
            f"{num_heads} d) of one dtype, (taps, {num_heads} d), and "
            "(B, S)")
    blocks = (_blocks(s, num_heads, _widths(xs, num_heads), taps,
                      xs[0].dtype)
              if use_pallas(use_pallas_override) else None)
    _calls["calls"] += 1
    _calls["kernel_calls"] += blocks is not None
    if blocks is None:
        return stage_conv_heads_reference(xs, ws, num_heads, scales, ids,
                                          masks)
    return _stage(xs, ws, ids, (num_heads, scales, *blocks))
