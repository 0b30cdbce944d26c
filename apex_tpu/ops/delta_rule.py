"""The gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692) or one decay a head (Gated DeltaNet,
arXiv:2412.06464), chunkwise-parallel, forward and backward.

A head keeps a state `S` of (d_k, d_v), zero before the first token,
and a token does

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with `g_t` <= 0 a log-decay a channel of k, or one for every channel
(g of (B, n, S)), and `beta_t` a scalar in (0, 2).
`gated_delta_rule_reference` is that recurrence, a token at a time;
nobody trains on it.  `gated_delta_rule` computes the same in chunks
of `chunk` tokens:

* inside a chunk (`_locals`, or on the chip the Pallas pair
  `kda_locals_fwd` / `kda_locals_bwd`; every chunk of every head): with
  `G_t` the running sum of g from the chunk's first token, a token's
  update is `S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T` with the
  pseudo-value `u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t)`,
  and the u of a chunk solve `(I + A) U = beta (V - K+ S_0)`, A
  strictly lower triangular, `A_ti = beta_t sum_d k_t k_i
  exp(G_t - G_i)`, `K+ = k exp(G)`.  So `U = u - w S_0` with
  `u = T (beta V)`, `w = T (beta K+)`, `T = (I + A)^-1` (the WY / UT
  transform; `_tri_inv` inverts by halves);
* between chunks (`_states`, a `lax.scan` over the chunks, float32):
  `S_next = Diag(exp(G_C)) S + Kd^T (u - w S)`, `Kd = k exp(G_C - G)`;
* the outputs (`_outputs`, every chunk at once, from the states at the
  chunks' starts): `o = Q+ S + tril(QK) (u - w S)`, `Q+ = q exp(G)`,
  `QK_ti = sum_d q_t k_i exp(G_t - G_i)`.

Decays are only ever applied as `exp` of a difference that is <= 0:
`exp(G_t - G_i)` of a pair more than a sub-block of 16 tokens apart is
split at the later sub-block's start into two such factors and the sum
over channels is a matmul; inside a sub-block the difference is taken
explicitly, a pair and channel at a time (`_pair_products`).  With one
decay a head the op takes g as a channel's decay of one channel, which
every product broadcasts over d_k, and a chunk's decay is one (C, C)
matrix `exp(G_t - G_i)`, t >= i, shared by all channels, times the
products over channels (`_scalar_pair_products`, in the kernels
`_scalar_pairs`): nothing is split.  Nothing is divided by a decay, so
a channel that forgets everything within a chunk costs no accuracy and
overflows nowhere.

The op is a `jax.custom_vjp`.  The forward keeps its inputs and the
states at the chunks' starts, S / chunk states a head, not S; the
backward recomputes what is local to a chunk from them, pulls the
output's cotangent back through `_outputs`, runs the recurrence's
transpose over the chunks in reverse, and pulls the sum back through
`_locals`: each piece is the `jax.vjp` of the forward's own function,
so the two cannot drift apart.

Documents packed into one row (`resets`, a (B, S) mask of the tokens
that start one): a head's state is zero before such a token, as before
the row's first.  The op has no second mechanism for that: the token's
log-decay is pinned to `RESET_LOG_DECAY` on every channel, so what the
state held reaches the token multiplied by `exp(-30)`, 9e-14, which is
nothing beside anything a float32 sum holds, and every factor that
carries a pair, a chunk's state or a cotangent across the boundary has
that decay in its exponent: the chunk-local stage, the Pallas pair, the
scans over chunks and their pullbacks run as they are.  The decay a
first token came with is never read (`where`), so its gradient is 0,
as under an exact reset, where nothing is left to decay.  The price is
resolution: a chunk's running sums of g are 30 larger a boundary, and
a float32 near 60 (two boundaries in a chunk) resolves 4e-6, which the
decay between two tokens of one document past a boundary then carries
as a relative error; the decays of a channel that forgets within a
chunk sum as large without any boundary.  A lower pin would buy
nothing and cost resolution; one above -25 would leave a state a
hundred times its successor visible in the seventh digit.
`gated_delta_rule_reference` resets exactly.

What runs where, all of it under the caller's `attn/scan` scope.  What
is local to a chunk is two Pallas kernels on the chip, at head widths
that fill three quarters of their lane tiles or more (a block's last
dimension is a head's whole width: 96-wide keys and 192-wide values go
as they are, padded to lane tiles in VMEM alone; a 64-wide head does
not go) and a chunk of 32, 64 or 128 (`_kernels_take`), for either kind
of decay: `kda_locals_fwd` reads q, k, v, g, beta once and writes the
six arrays of `_Locals` once, `kda_locals_bwd`
reads them and the six cotangents and writes the five gradients, and
nothing between goes through HBM.  Any other call, and every call off
the chip, takes `_locals`, compiled `jax.numpy` and the kernels'
oracle.  The scans over chunks and `_outputs` are `jax.numpy` on either
path.  Matrix products take their operands in the dtype of q (bf16 in
a bf16 model) and accumulate in float32; running sums of g, decays,
the sums over channels inside a sub-block (with a head's decay, all of
them), the triangular inverse and the state between chunks are
float32 whatever q is, in the kernels as in `_locals`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

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
    round_up,
    use_pallas,
)

_SUB = 16            # tokens a sub-block: pairs inside one are explicit
DEFAULT_CHUNK = 64
# the log-decay of a token that starts a document, every channel
RESET_LOG_DECAY = -30.0

# calls traced since the last reset, the chunk of the last of them and
# the bytes of chunk-start states their forwards keep for the backward
_calls = {"calls": 0, "chunk": 0, "saved_state_bytes": 0,
          "kernel_calls": 0, "scalar_calls": 0, "scalar_kernel_calls": 0,
          "state_elems": 0, "state_lane_elems": 0}


def stats():
    """{"calls": `gated_delta_rule` calls traced since the last reset
    (a call that was differentiated counts once), "chunk": the chunk of
    the last of them, "saved_state_bytes": the bytes of chunk-start
    states the forwards of those calls keep for their backwards, summed
    over the calls, "kernel_calls": those of "calls" whose chunk-local
    stage took the Pallas pair, "scalar_calls": those of "calls" with
    one decay a head, "scalar_kernel_calls": those of them that took
    the pair, "state_elems": a head's d_k x d_v state elements, summed
    over the calls, "state_lane_elems": the elements the state's
    (d_k, d_v) float32 holds in the (8, 128) tiles it lies in, summed
    the same way (a head of 96 x 192 holds 96 x 256: 75% of them
    carry)}."""
    return dict(_calls)


def reset_stats():
    for key in _calls:
        _calls[key] = 0



# ------------------------------ the recurrence ------------------------------

def gated_delta_rule_reference(q, k, v, g, beta, resets=None):
    """The recurrence itself, a token at a time, in float32: the parity
    oracle.  Shapes as `gated_delta_rule`, g a channel's (B, n, S, d_k)
    or a head's (B, n, S); the state is set to zero, exactly, before a
    token `resets` (B, S) marks."""
    f32 = jnp.float32
    if g.ndim == 3:
        g = g[..., None]
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    keep = (jnp.ones_like(beta) if resets is None else
            jnp.broadcast_to(~resets[:, None], beta.shape).astype(f32))

    def token(state, x):
        qt, kt, vt, gt, bt, keep_t = x          # (B, n, d) ... (B, n)
        state = state * (keep_t[..., None] * jnp.exp(gt))[..., None]
        seen = jnp.einsum("bnkv,bnk->bnv", state, kt)
        state = state + jnp.einsum(
            "bnk,bnv->bnkv", kt, bt[..., None] * (vt - seen))
        return state, jnp.einsum("bnkv,bnk->bnv", state, qt)

    b, n, _, dk = q.shape
    first = jnp.zeros((b, n, dk, v.shape[-1]), f32)
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta, keep))
    with jax.default_matmul_precision("highest"):
        _, o = lax.scan(token, first, xs)
    return jnp.moveaxis(o, 0, 2)


# ------------------------------- inside a chunk ------------------------------

class _Locals(NamedTuple):
    """What `_states` and `_outputs` need of a chunk, (N, B, n, ...),
    the chunk first as the scans over chunks read them: qp = q exp(G)
    (C, d_k); kd = k exp(G_C - G) (C, d_k); decay = exp(G_C) (d_k,); w
    (C, d_k) and u (C, d_v), the chunk's pseudo-values as `u - w S_0`;
    qk (C, C), lower triangle with its diagonal.  decay and u are
    float32, the others q's dtype."""
    qp: jnp.ndarray
    kd: jnp.ndarray
    decay: jnp.ndarray
    w: jnp.ndarray
    u: jnp.ndarray
    qk: jnp.ndarray


def _mm(spec, a, b, dtype):
    """einsum of a and b as `dtype` operands, accumulated in float32."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


@jax.checkpoint
def _within_sub_blocks(q, k, G):
    """(kk, qk), each (..., c, c): `sum_d x_t k_i exp(G_t - G_i)` for
    x = k, q over the pairs t >= i of one sub-block, 0 above the
    diagonal.  The difference is taken before the exp, a pair and
    channel at a time: (c, c, d_k) terms a sub-block that the compiler
    is to reduce as it makes them, here and (hence the checkpoint)
    in the backward."""
    c = G.shape[-2]
    lower = jnp.tril(jnp.ones((c, c), bool))[..., None]
    spread = jnp.exp(jnp.where(
        lower, G[..., :, None, :] - G[..., None, :, :], -jnp.inf))
    ke = k[..., None, :, :] * spread
    return (jnp.sum(k[..., :, None, :] * ke, axis=-1),
            jnp.sum(q[..., :, None, :] * ke, axis=-1))


def _pair_products(q, k, G, dtype):
    """(kk, qk), each (..., C, C): `sum_d x_t k_i exp(G_t - G_i)` for
    x = k, q over the pairs t >= i of a chunk, 0 above the diagonal.
    q, k, G: (..., C, d_k) float32, G the running sum of g inside the
    chunk."""
    *lead, C, dk = G.shape
    c = min(_SUB, C)
    m = C // c
    sub = lambda x: x.reshape(*lead, m, c, dk)
    kk_d, qk_d = _within_sub_blocks(sub(q), sub(k), sub(G))
    rows_kk, rows_qk = [], []
    for a in range(m):
        here = slice(a * c, (a + 1) * c)
        parts_kk, parts_qk = [kk_d[..., a, :, :]], [qk_d[..., a, :, :]]
        if a:
            # split at the sub-block's start: both factors decay
            at = G[..., a * c - 1:a * c, :]
            right = k[..., :a * c, :] * jnp.exp(at - G[..., :a * c, :])
            turn = jnp.exp(G[..., here, :] - at)
            parts_kk.insert(0, _mm("...td,...id->...ti",
                                   k[..., here, :] * turn, right, dtype))
            parts_qk.insert(0, _mm("...td,...id->...ti",
                                   q[..., here, :] * turn, right, dtype))
        if a < m - 1:
            above = jnp.zeros((*lead, c, C - (a + 1) * c), jnp.float32)
            parts_kk.append(above)
            parts_qk.append(above)
        rows_kk.append(jnp.concatenate(parts_kk, axis=-1))
        rows_qk.append(jnp.concatenate(parts_qk, axis=-1))
    return (jnp.concatenate(rows_kk, axis=-2),
            jnp.concatenate(rows_qk, axis=-2))


def _scalar_pair_products(q, k, G):
    """`_pair_products` where the decay is a head's, G (..., C, 1): the
    chunk's one (C, C) matrix `exp(G_t - G_i)`, t >= i, shared by every
    channel, times the products over channels, float32."""
    C = G.shape[-2]
    lower = jnp.tril(jnp.ones((C, C), bool))
    diff = G[..., :, :1] - jnp.swapaxes(G, -1, -2)
    spread = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.)), 0.)
    hi = lax.Precision.HIGHEST
    return (jnp.einsum("...td,...id->...ti", k, k, precision=hi) * spread,
            jnp.einsum("...td,...id->...ti", q, k, precision=hi) * spread)


def _tri_inv(a):
    """(I + a)^-1 for a strictly lower triangular, (..., m, m) float32,
    m a power of two: by halves, [[P, 0], [R, Q]]^-1 = [[P^-1, 0],
    [-Q^-1 R P^-1, Q^-1]], every block of a size at once: the inverses
    of the diagonal blocks of size 1 are ones, and each of the log2(m)
    rounds joins neighbours into blocks twice the size.  Exact in m - 1
    steps' worth of products: no series is summed."""
    *lead, m, _ = a.shape
    hi = lax.Precision.HIGHEST
    inv = jnp.ones((*lead, m, 1, 1), a.dtype)      # (..., blocks, h, h)
    h = 1
    while h < m:
        nb = m // (2 * h)
        # the lower-left (h, h) corner of every diagonal (2h, 2h) block
        blocks = a.reshape(*lead, nb, 2, h, nb, 2, h)
        corner = jnp.moveaxis(jnp.diagonal(
            blocks[..., :, 1, :, :, 0, :], axis1=-4, axis2=-2), -1, -3)
        pairs = inv.reshape(*lead, nb, 2, h, h)
        p, q = pairs[..., 0, :, :], pairs[..., 1, :, :]
        r = -jnp.einsum("...ij,...jk,...kl->...il", q, corner, p,
                        precision=hi)
        top = jnp.concatenate([p, jnp.zeros_like(p)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([r, q], axis=-1)], axis=-2)
        h *= 2
    return inv[..., 0, :, :]


def _locals(q, k, v, g, beta, chunk):
    """Everything of a chunk that does not read the state: _Locals."""
    b, n, s, dk = k.shape
    dtype = q.dtype
    f32 = jnp.float32
    cut = lambda x: x.reshape(b, n, s // chunk, chunk, *x.shape[3:])
    q, k, v, g, beta = (cut(x.astype(f32)) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=3)
    fade = jnp.exp(G)
    end = G[..., -1:, :]
    if G.shape[-1] == 1:
        kk, qk = _scalar_pair_products(q, k, G)
    else:
        kk, qk = _pair_products(q, k, G, dtype)
    A = beta[..., None] * jnp.tril(kk, -1)
    T = _tri_inv(A)
    w = _mm("...ti,...id->...td", T, beta[..., None] * (k * fade), dtype)
    u = _mm("...ti,...id->...td", T, beta[..., None] * v, dtype)
    # what is only ever a matmul's operand is kept as the matmul takes
    # it: the same numbers in half the bytes where q is bf16
    loc = _Locals(qp=(q * fade).astype(dtype),
                  kd=(k * jnp.exp(end - G)).astype(dtype),
                  decay=jnp.exp(end[..., 0, :]), w=w.astype(dtype), u=u,
                  qk=qk.astype(dtype))
    return _Locals(*(jnp.moveaxis(x, 2, 0) for x in loc))


# ------------------------- inside a chunk, in VMEM --------------------------
#
# `_locals` once more, for the chip: a step of the grid holds `_CHUNKS`
# chunks of one head in VMEM as (chunks * C, d) tiles.  What `_locals`
# spells with cumsum, reshape, concatenate and diagonal is here a shift
# along the sublanes, a select under a mask of the tile's indices or a
# matrix product batched over the chunks, so that every step lowers and
# `jax.vjp` of the same function, inside the second kernel's body, is
# the pullback: the two cannot drift apart.
#
# * Sums of g (`_block_sums`): for h = 1, 2, 4, ... C, the sum from the
#   start of a token's block of h tokens to the token, and over its
#   whole block, by doubling; float32.  Every decay is exp of one of
#   them or of a difference of two, never positive while g is not.
# * The pairs of one sub-block, float32: by halves, as `_tri_inv` goes.
#   At a level of h tokens a pair (t, i) with t in the upper and i in
#   the lower half of one block of 2h splits at the upper half's start
#   into `exp(sum of g over [start, t])` and `exp(sum of g over (i,
#   start))`, both <= 1, and the sum over channels is a float32 matrix
#   product at HIGHEST masked to those pairs; log2(16) levels cover
#   every pair below the diagonal.  The difference is still taken
#   before the exp; nothing is divided by a decay.
# * The pairs of two sub-blocks: as `_pair_products` splits them, at
#   the later sub-block's start, operands in q's dtype.
# * `(I + A)^-1` (`_unit_lower_inverse`): `_tri_inv`'s rounds on (C, C)
#   tiles, D <- D - D (A * corner_h) D with corner_h the lower-left
#   corners of the diagonal blocks of 2h, the level's own mask.

_CHUNKS = 8          # chunks a grid step, as far as they divide S / chunk
_KERNEL_CHUNKS = (32, 64, 128)
_HI = lax.Precision.HIGHEST


def _doublings(top):
    """1, 2, 4, ... below top."""
    return [1 << i for i in range(top.bit_length() - 1)]


def _corner(C, h):
    """(C, C) bool: t in the upper and i in the lower half of one block
    of 2h tokens."""
    t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    i = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return ((t ^ i) < 2 * h) & ((t & h) != 0) & ((i & h) == 0)


def _across(C, a):
    """(C, C) bool: t in sub-block a, i in a sub-block before it."""
    t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    i = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return (t >= a * _SUB) & (t < (a + 1) * _SUB) & (i < a * _SUB)


def _eye(C):
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0)
            == lax.broadcasted_iota(jnp.int32, (C, C), 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rows_down(x, shift):
    """x[r - shift] at row r, around the end."""
    return pltpu.roll(x, shift, 0)


def _rows_down_fwd(x, shift):
    return _rows_down(x, shift), None


def _rows_down_bwd(shift, _, ct):
    return (pltpu.roll(ct, ct.shape[0] - shift, 0),)


_rows_down.defvjp(_rows_down_fwd, _rows_down_bwd)


def _block_sums(g, C):
    """{h: (P, B)} for h = 1, 2, ... C over g (chunks * C, d_k): P the
    sum of g from the start of a token's block of h tokens to the
    token, B over its whole block.  A doubling joins neighbours: the
    rows a shift brings in from another chunk are never kept."""
    rows = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    P = B = g
    out = {1: (P, B)}
    for h in _doublings(C):
        upper = (rows & h) != 0
        below = _rows_down(B, h)
        above = _rows_down(B, g.shape[0] - h)
        P = P + jnp.where(upper, below, 0.)
        B = B + jnp.where(upper, below, above)
        out[2 * h] = (P, B)
    return out


def _bmm(a, b, **kw):
    """(n, i, j) x (n, j, k) -> (n, i, k), accumulated in float32."""
    return lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                           preferred_element_type=jnp.float32, **kw)


def _pairs(a, b, **kw):
    """(n, t, d) x (n, i, d) -> (n, t, i): the sum over channels."""
    return lax.dot_general(a, b, (((2,), (2,)), ((0,), (0,))),
                           preferred_element_type=jnp.float32, **kw)


def _inverse_rounds(A):
    C = A.shape[-1]
    T = jnp.where(_eye(C), 1., 0.) - jnp.where(_corner(C, 1), A, 0.)
    for h in _doublings(C)[1:]:
        T = T - _bmm(_bmm(T, jnp.where(_corner(C, h), A, 0.), precision=_HI),
                     T, precision=_HI)
    return T


@jax.custom_vjp
def _unit_lower_inverse(A):
    """(I + tril(A, -1))^-1 of (n, C, C) float32 tiles."""
    return _inverse_rounds(A)


def _unit_lower_inverse_fwd(A):
    T = _inverse_rounds(A)
    return T, T


def _unit_lower_inverse_bwd(T, dT):
    # d(X^-1) = -X^-1 dX X^-1, transposed: dA = -T^t dT T^t
    left = lax.dot_general(T, dT, (((1,), (1,)), ((0,), (0,))),
                           precision=_HI, preferred_element_type=jnp.float32)
    return (-_pairs(left, T, precision=_HI),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _chunks_locals(q, k, v, g, beta, C):
    """`_locals` of n chunks side by side: q, k (n C, d_k), v (n C,
    d_v) in q's dtype, g (n C, d_k) and beta (n C, 1) float32 -> the
    six of `_Locals`, in its order and dtypes, (n, C, .) each and decay
    (n, 1, d_k)."""
    dtype = q.dtype
    f32 = jnp.float32
    n = g.shape[0] // C
    q, k, v = (x.astype(f32) for x in (q, k, v))
    by_chunk = lambda x: x.reshape(n, C, x.shape[-1])
    sums = _block_sums(g, C)
    G, whole = sums[C]
    fade = jnp.exp(G)
    if g.shape[-1] == 1:
        kk, qk = _scalar_pairs(by_chunk(q), by_chunk(k), by_chunk(G))
    else:
        kk, qk = _channel_pairs(q, k, sums, dtype, C)
    T = _unit_lower_inverse(by_chunk(beta) * kk).astype(dtype)
    w = _bmm(T, by_chunk((beta * (k * fade)).astype(dtype)))
    u = _bmm(T, by_chunk((beta * v).astype(dtype)))
    return _Locals(
        qp=by_chunk((q * fade).astype(dtype)),
        kd=by_chunk((k * jnp.exp(whole - G)).astype(dtype)),
        decay=jnp.exp(jnp.sum(by_chunk(g), axis=1, keepdims=True)),
        w=w.astype(dtype), u=u, qk=qk.astype(dtype))


def _scalar_pairs(q, k, G):
    """(kk, qk), (n, C, C), of a head's decay: G (n, C, 1), q and k (n,
    C, d_k) float32.  The chunk's one matrix `exp(G_t - G_i)`, t >= i,
    times the products over channels, both float32 at HIGHEST."""
    n, C, _ = G.shape
    # G_i along a row: the column across the lanes, turned
    along = jnp.swapaxes(jnp.broadcast_to(G, (n, C, C)), 1, 2)
    t = lax.broadcasted_iota(jnp.int32, (n, C, C), 1)
    i = lax.broadcasted_iota(jnp.int32, (n, C, C), 2)
    lower = t >= i
    spread = jnp.where(lower, jnp.exp(jnp.where(lower, G - along, 0.)), 0.)
    both = _pairs(jnp.concatenate([k, q], axis=1), k, precision=_HI)
    # kk below the diagonal only, as the inverse reads it: its pullback
    # returns a whole matrix
    return jnp.where(t > i, both[:, :C] * spread, 0.), both[:, C:] * spread


def _channel_pairs(q, k, sums, dtype, C):
    """(kk, qk), (n, C, C), of a channel's decay: by halves inside a
    sub-block, split at the later one's start between them."""
    f32 = jnp.float32
    n = q.shape[0] // C
    by_chunk = lambda x: x.reshape(n, C, x.shape[-1])
    G = sums[C][0]
    # inside a sub-block, float32, by halves; the diagonal is no decay
    kk = jnp.zeros((n, C, C), f32)
    qk = jnp.where(_eye(C),
                   by_chunk(jnp.sum(q * k, axis=-1, keepdims=True)), 0.)

    def add(keep, left, right, **kw):
        # k's and q's rows against the same columns in one product
        both = _pairs(jnp.concatenate(left, axis=1), right, **kw)
        return (kk + jnp.where(keep, both[:, :C], 0.),
                qk + jnp.where(keep, both[:, C:], 0.))

    for h in _doublings(_SUB):
        turn = jnp.exp(sums[h][0])
        right = k * jnp.exp(sums[h][1] - sums[h][0]) if h > 1 else k
        kk, qk = add(_corner(C, h), [by_chunk(k * turn), by_chunk(q * turn)],
                     by_chunk(right), precision=_HI)
    # between sub-blocks, split at the later one's start
    turn = jnp.exp(sums[_SUB][0])
    left = [by_chunk((k * turn).astype(dtype)),
            by_chunk((q * turn).astype(dtype))]
    rows = lax.broadcasted_iota(jnp.int32, (1, C, 1), 1)
    for a in range(1, C // _SUB):
        start = jnp.sum(jnp.where(rows == a * _SUB - 1, by_chunk(G), 0.),
                        axis=1, keepdims=True)
        right = by_chunk(k) * jnp.exp(jnp.minimum(start - by_chunk(G), 0.))
        kk, qk = add(_across(C, a), left, right.astype(dtype))
    return kk, qk


# a grid step's blocks: the inputs and their gradients head-major, (1,
# n, C, .) of (B n, N, C, .); `_Locals` and its cotangents chunk-major,
# (n, 1, C, .) of (N, B n, C, .), as the scans over chunks read them

def _rows(ref):
    """A head-major block with its chunks' rows in one axis."""
    return ref[0].reshape(-1, ref.shape[-1])


def _locals_fwd_kernel(*refs, C):
    ins, outs = refs[:5], refs[5:]
    for ref, x in zip(outs, _chunks_locals(*map(_rows, ins), C)):
        ref[:, 0] = x


def _locals_bwd_kernel(*refs, C):
    ins, cots, outs = refs[:5], refs[5:11], refs[11:]
    _, pull = jax.vjp(lambda *x: _chunks_locals(*x, C), *map(_rows, ins))
    for ref, x in zip(outs, pull(_Locals(*(r[:, 0] for r in cots)))):
        ref[0] = x.reshape(ref.shape[1:])


def _locals_call(backward, q, v, g, chunk):
    """The `pl.pallas_call` of a direction at q's, v's and g's shapes:
    from q, k, v, g, beta (then `_Locals`' six cotangents) to `_Locals`'
    six (or the five gradients).  A block's last dimension is a head's
    whole width, as `_kernels_take` admits it, and g's is d_k or, a
    head's decay, 1."""
    b, n, s, dk = q.shape
    bn, n_chunks, dv, dg = b * n, s // chunk, v.shape[-1], g.shape[-1]
    nc = next(c for c in (_CHUNKS, 4, 2, 1) if n_chunks % c == 0)
    dtype, f32 = jnp.dtype(q.dtype), jnp.float32

    def head_major(minor, dt):
        return (pl.BlockSpec((1, nc, *minor), lambda i, j: (i, j, 0, 0)),
                jax.ShapeDtypeStruct((bn, n_chunks, *minor), dt))

    def chunk_major(minor, dt):
        return (pl.BlockSpec((nc, 1, *minor), lambda i, j: (j, i, 0, 0)),
                jax.ShapeDtypeStruct((n_chunks, bn, *minor), dt))

    ins = [head_major(*x) for x in (
        ((chunk, dk), dtype), ((chunk, dk), dtype), ((chunk, dv), dtype),
        ((chunk, dg), f32), ((chunk, 1), f32))]
    locs = _Locals(*(chunk_major(*x) for x in (
        ((chunk, dk), dtype), ((chunk, dk), dtype), ((1, dg), f32),
        ((chunk, dk), dtype), ((chunk, dv), f32), ((chunk, chunk), dtype))))
    reads, writes = (ins + list(locs), ins) if backward else (ins, locs)
    how = dict(
        grid=(bn, n_chunks // nc),
        in_specs=[spec for spec, _ in reads],
        out_specs=[spec for spec, _ in writes],
        out_shape=[shape for _, shape in writes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=96 * 2 ** 20),
        interpret=pallas_interpret())
    if backward:
        return pl.pallas_call(
            functools.partial(_locals_bwd_kernel, C=chunk),
            name="kda_locals_bwd", **how)
    return pl.pallas_call(
        functools.partial(_locals_fwd_kernel, C=chunk),
        name="kda_locals_fwd", **how)


def _cut(q, k, v, g, beta, chunk):
    """The kernels' inputs: (B, n, S, ...) -> (B n, S / chunk, chunk,
    ...), g and beta float32, beta a column."""
    b, n, s, _ = q.shape
    f32 = jnp.float32
    return tuple(x.reshape(b * n, s // chunk, chunk, -1)
                 for x in (q, k, v, g.astype(f32), beta.astype(f32)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _locals_kernels(q, k, v, g, beta, chunk):
    """`_locals` by `kda_locals_fwd`, its pullback by `kda_locals_bwd`."""
    return _locals_kernels_fwd(q, k, v, g, beta, chunk)[0]


def _locals_kernels_fwd(q, k, v, g, beta, chunk):
    b, n = q.shape[:2]
    with kernel_span("kda_locals_fwd"):
        locs = _locals_call(False, q, v, g, chunk)(
            *_cut(q, k, v, g, beta, chunk))
    loc = _Locals(*(
        x.reshape(x.shape[0], b, n, *x.shape[2:]) for x in locs))
    return loc._replace(decay=loc.decay[..., 0, :]), (q, k, v, g, beta)


def _locals_kernels_bwd(chunk, res, d_loc):
    q, _, v, g = res[:4]
    d_loc = d_loc._replace(decay=d_loc.decay[..., None, :])
    with kernel_span("kda_locals_bwd"):
        grads = _locals_call(True, q, v, g, chunk)(
            *_cut(*res, chunk),
            *(x.reshape(x.shape[0], -1, *x.shape[3:]) for x in d_loc))
    return tuple(dx.reshape(x.shape).astype(x.dtype)
                 for dx, x in zip(grads, res))


_locals_kernels.defvjp(_locals_kernels_fwd, _locals_kernels_bwd)


def _kernels_take(q, v, chunk, override):
    """Whether a call's chunk-local stage takes the Pallas pair: on the
    chip (or where a test says so), q and v of one dtype, a chunk the
    kernels are written for and heads whose widths fill their lane
    tiles (`fills_lane_tiles`: a block's last dimension is a head's
    whole width, which Mosaic takes as the array's own and pads to lane
    tiles in VMEM alone)."""
    return (use_pallas(override) and v.dtype == q.dtype
            and chunk in _KERNEL_CHUNKS and fills_lane_tiles(q.shape[-1])
            and fills_lane_tiles(v.shape[-1]))


# ------------------------------ between chunks ------------------------------

def _advance(state, decay, kd, w, u, dtype):
    """The state a chunk leaves, from the state it finds: (B, n, d_k,
    d_v) float32."""
    new = u - _mm("...td,...dv->...tv", w, state, dtype)
    return decay[..., None] * state + _mm("...td,...tv->...dv", kd, new,
                                          dtype)


def _states(loc, dtype):
    """The state at every chunk's start, (N, B, n, d_k, d_v) float32:
    zero at the first."""
    _, b, n, _, dk = loc.kd.shape
    first = jnp.zeros((b, n, dk, loc.u.shape[-1]), jnp.float32)

    def chunk(state, x):
        return _advance(state, *x, dtype), state

    return lax.scan(chunk, first, (loc.decay, loc.kd, loc.w, loc.u))[1]


def _outputs(loc, starts, dtype):
    """o of every token, (N, B, n, C, d_v) float32."""
    new = loc.u - _mm("...td,...dv->...tv", loc.w, starts, dtype)
    return (_mm("...td,...dv->...tv", loc.qp, starts, dtype)
            + _mm("...ti,...iv->...tv", loc.qk, new, dtype))


# ---------------------------------- the op ----------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _delta_rule(q, k, v, g, beta, chunk, kernels):
    return _delta_rule_fwd(q, k, v, g, beta, chunk, kernels)[0]


def _locals_and_pullback(q, k, v, g, beta, chunk, kernels):
    """(`_Locals`, its pullback), as `jax.vjp` of the chunk-local stage
    gives them.  The Pallas pair's two rules are called as they are: a
    `jax.vjp` in here would wrap the kernels' names in the trace
    (`transpose(jvp(kda_locals_bwd))`) and hide them from its readers."""
    if not kernels:
        return jax.vjp(lambda *x: _locals(*x, chunk), q, k, v, g, beta)
    loc, res = _locals_kernels_fwd(q, k, v, g, beta, chunk)
    return loc, lambda d_loc: _locals_kernels_bwd(chunk, res, d_loc)


def _delta_rule_fwd(q, k, v, g, beta, chunk, kernels=False):
    loc = (_locals_kernels if kernels else _locals)(q, k, v, g, beta, chunk)
    starts = _states(loc, q.dtype)
    o = jnp.moveaxis(_outputs(loc, starts, q.dtype).astype(v.dtype), 0, 2)
    b, n, s, _ = q.shape
    return o.reshape(b, n, s, -1), (q, k, v, g, beta, starts)


def _delta_rule_bwd(chunk, kernels, res, do):
    q, k, v, g, beta, starts = res
    dtype = q.dtype
    b, n, s, _ = q.shape
    loc, pull_locals = _locals_and_pullback(q, k, v, g, beta, chunk, kernels)
    do = jnp.moveaxis(do.reshape(b, n, s // chunk, chunk, -1), 2, 0)
    _, pull_outputs = jax.vjp(
        lambda loc, starts: _outputs(loc, starts, dtype), loc, starts)
    d_loc, d_starts = pull_outputs(do.astype(jnp.float32))

    # the recurrence's transpose: from the last chunk to the first,
    # carrying the cotangent of the state a chunk leaves
    def chunk_back(d_left, x):
        state, d_start, *locals_ = x
        _, pull = jax.vjp(
            lambda state, *l: _advance(state, *l, dtype), state, *locals_)
        d_state, *d_locals = pull(d_left)
        return d_state + d_start, tuple(d_locals)

    _, (d_decay, d_kd, d_w, d_u) = lax.scan(
        chunk_back, jnp.zeros_like(starts[0]),
        (starts, d_starts, loc.decay, loc.kd, loc.w, loc.u), reverse=True)
    d_loc = d_loc._replace(decay=d_loc.decay + d_decay, kd=d_loc.kd + d_kd,
                           w=d_loc.w + d_w, u=d_loc.u + d_u)
    return pull_locals(d_loc)


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def gated_delta_rule(q, k, v, g, beta, *, resets=None,
                     chunk: Optional[int] = None,
                     heads_a_pass: Optional[int] = None,
                     use_pallas_override: Optional[bool] = None):
    """o (B, n, S, d_v) of the gated delta rule over head-major q, k
    (B, n, S, d_k), v (B, n, S, d_v), the log-decay g, <= 0, of a
    channel (B, n, S, d_k) or of a head (B, n, S), and beta (B, n, S);
    every head starts from a zero state.  o has v's dtype; g and beta
    are best handed over in float32, and g's gradient comes back in
    g's shape.

    `resets`: (B, S) bool, true at the tokens that start a document of
    a packed row: a head's state is zero before each, and g gets no
    gradient there (the module's docstring says how).  None is one
    document a row, and the op as it is without the argument.

    `chunk`: tokens a chunk, a power of two that divides S; None asks
    the `apex_tpu.tune` cache for one measured at this shape (op
    `delta_rule`, key `tune.delta_rule_attrs`) and takes DEFAULT_CHUNK,
    or the largest power of two under it that divides S, on a miss.
    The forward keeps S / chunk states of (d_k, d_v) float32 a head for
    the backward (`stats()["saved_state_bytes"]`), and recomputes the
    rest of a chunk there.

    `heads_a_pass`: heads computed at a time, a divisor of n: so many
    calls of the op side by side, each with the float32 temporaries of
    its own heads' recompute only, which the compiler's scheduler then
    need not hold together (a tuned config's `heads`, if it has one;
    None is every head in one call).

    On the chip the chunk-local stage of a call at a chunk of 32, 64
    or 128 is the Pallas pair `kda_locals_fwd` / `kda_locals_bwd` where
    the heads' widths fill their lane tiles (96, 192, a multiple of
    128: `_kernels_take`), elsewhere compiled `jax.numpy`
    (`stats()["kernel_calls"]` counts the former, "scalar_calls" and
    "scalar_kernel_calls" the calls with a head's decay);
    `use_pallas_override` is for the tests: True runs the kernels in
    interpret mode off the chip, False keeps `jax.numpy` on it."""
    b, n, s, dk = q.shape
    if k.shape != q.shape or g.shape not in (q.shape, (b, n, s)) \
            or v.shape[:3] != (b, n, s) or beta.shape != (b, n, s):
        raise ValueError(
            f"shapes q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, "
            f"beta {beta.shape} are not (B, n, S, d_k) x 2, (B, n, S, "
            "d_v), (B, n, S, d_k) or (B, n, S), (B, n, S)")
    scalar = g.ndim == 3
    if scalar:
        # a head's decay is a channel's of one channel, which every
        # product broadcasts over d_k; its gradient comes back (B, n, S)
        g = g[..., None]
    if resets is not None:
        if resets.shape != (b, s):
            raise ValueError(f"resets {resets.shape} is not (B, S) = "
                             f"{(b, s)}")
        g = jnp.where(resets[:, None, :, None],
                      jnp.asarray(RESET_LOG_DECAY, g.dtype), g)
    if chunk is None:
        from apex_tpu import tune

        cfg = tune.tuned("delta_rule", tune.delta_rule_attrs(
            b, n, s, dk, v.shape[-1], q.dtype))
        chunk = int(cfg["chunk"]) if cfg else DEFAULT_CHUNK
        while s % chunk:
            chunk //= 2
        if cfg and heads_a_pass is None:
            heads_a_pass = cfg.get("heads")
    if chunk < 1 or chunk & (chunk - 1) or s % chunk:
        raise ValueError(f"chunk {chunk} is not a power of two that "
                         f"divides the sequence, {s}")
    kernels = _kernels_take(q, v, chunk, use_pallas_override)
    _calls["calls"] += 1
    _calls["chunk"] = chunk
    _calls["saved_state_bytes"] += 4 * b * n * (s // chunk) * dk * v.shape[-1]
    _calls["kernel_calls"] += kernels
    _calls["scalar_calls"] += scalar
    _calls["scalar_kernel_calls"] += scalar and kernels
    _calls["state_elems"] += dk * v.shape[-1]
    _calls["state_lane_elems"] += round_up(dk, 8) * round_up(v.shape[-1],
                                                             LANES)
    if heads_a_pass in (None, n):
        return _delta_rule(q, k, v, g, beta, chunk, kernels)
    if heads_a_pass < 1 or n % heads_a_pass:
        raise ValueError(f"heads_a_pass {heads_a_pass} does not divide the "
                         f"{n} heads")

    # one call a pass, written out: a loop instruction around them
    # would hide its body from a reader of the trace and carry its
    # operands through copies
    return jnp.concatenate([
        _delta_rule(*(x[:, h:h + heads_a_pass] for x in (q, k, v, g, beta)),
                    chunk, kernels)
        for h in range(0, n, heads_a_pass)], axis=1)
