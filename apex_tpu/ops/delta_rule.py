"""The gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692), chunkwise-parallel, forward and backward.

A head keeps a state `S` of (d_k, d_v), zero before the first token,
and a token does

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with `g_t` <= 0 a log-decay a channel of k and `beta_t` a scalar in
(0, 2).  `gated_delta_rule_reference` is that recurrence, a token at a
time; nobody trains on it.  `gated_delta_rule` computes the same in
chunks of `chunk` tokens:

* inside a chunk (`_locals`, every chunk of every head at once): with
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
explicitly, a pair and channel at a time (`_pair_products`).  Nothing
is divided by a decay, so a channel that forgets everything within a
chunk costs no accuracy and overflows nowhere.

The op is a `jax.custom_vjp`.  The forward keeps its inputs and the
states at the chunks' starts, S / chunk states a head, not S; the
backward recomputes what is local to a chunk from them, pulls the
output's cotangent back through `_outputs`, runs the recurrence's
transpose over the chunks in reverse, and pulls the sum back through
`_locals`: each piece is the `jax.vjp` of the forward's own function,
so the two cannot drift apart.

Everything here is `jax.numpy`: the compiled form is what runs on the
chip, under the caller's `attn/scan` scope.  Matrix products take
their operands in the dtype of q (bf16 in a bf16 model) and accumulate
in float32; running sums of g, decays, the triangular inverse and the
state between chunks are float32 whatever q is.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

_SUB = 16            # tokens a sub-block: pairs inside one are explicit
DEFAULT_CHUNK = 64

# calls traced since the last reset, the chunk of the last of them and
# the bytes of chunk-start states their forwards keep for the backward
_calls = {"calls": 0, "chunk": 0, "saved_state_bytes": 0}


def stats():
    """{"calls": `gated_delta_rule` calls traced since the last reset
    (a call that was differentiated counts once), "chunk": the chunk of
    the last of them, "saved_state_bytes": the bytes of chunk-start
    states the forwards of those calls keep for their backwards, summed
    over the calls}."""
    return dict(_calls)


def reset_stats():
    for key in _calls:
        _calls[key] = 0


# ------------------------------ the recurrence ------------------------------

def gated_delta_rule_reference(q, k, v, g, beta):
    """The recurrence itself, a token at a time, in float32: the parity
    oracle.  Shapes as `gated_delta_rule`."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))

    def token(state, x):
        qt, kt, vt, gt, bt = x                  # (B, n, d) ... (B, n)
        state = state * jnp.exp(gt)[..., None]
        seen = jnp.einsum("bnkv,bnk->bnv", state, kt)
        state = state + jnp.einsum(
            "bnk,bnv->bnkv", kt, bt[..., None] * (vt - seen))
        return state, jnp.einsum("bnkv,bnk->bnv", state, qt)

    b, n, _, dk = q.shape
    first = jnp.zeros((b, n, dk, v.shape[-1]), f32)
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        _, o = lax.scan(token, first, xs)
    return jnp.moveaxis(o, 0, 2)


# ------------------------------- inside a chunk ------------------------------

class _Locals(NamedTuple):
    """What `_states` and `_outputs` need of a chunk, (B, n, N, ...):
    qp = q exp(G) (C, d_k); kd = k exp(G_C - G) (C, d_k); decay =
    exp(G_C) (d_k,); w (C, d_k) and u (C, d_v), the chunk's pseudo-
    values as `u - w S_0`; qk (C, C), lower triangle with its
    diagonal.  decay and u are float32, the others q's dtype."""
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
    kk, qk = _pair_products(q, k, G, dtype)
    A = beta[..., None] * jnp.tril(kk, -1)
    T = _tri_inv(A)
    w = _mm("...ti,...id->...td", T, beta[..., None] * (k * fade), dtype)
    u = _mm("...ti,...id->...td", T, beta[..., None] * v, dtype)
    # what is only ever a matmul's operand is kept as the matmul takes
    # it: the same numbers in half the bytes where q is bf16
    return _Locals(qp=(q * fade).astype(dtype),
                   kd=(k * jnp.exp(end - G)).astype(dtype),
                   decay=jnp.exp(end[..., 0, :]), w=w.astype(dtype), u=u,
                   qk=qk.astype(dtype))


# ------------------------------ between chunks ------------------------------

def _advance(state, decay, kd, w, u, dtype):
    """The state a chunk leaves, from the state it finds: (B, n, d_k,
    d_v) float32."""
    new = u - _mm("...td,...dv->...tv", w, state, dtype)
    return decay[..., None] * state + _mm("...td,...tv->...dv", kd, new,
                                          dtype)


def _per_chunk(loc):
    """(decay, kd, w, u) with the chunk axis first, as a scan reads."""
    return tuple(jnp.moveaxis(x, 2, 0)
                 for x in (loc.decay, loc.kd, loc.w, loc.u))


def _states(loc, dtype):
    """The state at every chunk's start, (B, n, N, d_k, d_v) float32:
    zero at the first."""
    b, n, _, _, dk = loc.kd.shape
    first = jnp.zeros((b, n, dk, loc.u.shape[-1]), jnp.float32)

    def chunk(state, x):
        return _advance(state, *x, dtype), state

    _, starts = lax.scan(chunk, first, _per_chunk(loc))
    return jnp.moveaxis(starts, 0, 2)


def _outputs(loc, starts, dtype):
    """o of every token, (B, n, N, C, d_v) float32."""
    new = loc.u - _mm("...td,...dv->...tv", loc.w, starts, dtype)
    return (_mm("...td,...dv->...tv", loc.qp, starts, dtype)
            + _mm("...ti,...iv->...tv", loc.qk, new, dtype))


# ---------------------------------- the op ----------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _delta_rule(q, k, v, g, beta, chunk):
    return _delta_rule_fwd(q, k, v, g, beta, chunk)[0]


def _delta_rule_fwd(q, k, v, g, beta, chunk):
    loc = _locals(q, k, v, g, beta, chunk)
    starts = _states(loc, q.dtype)
    o = _outputs(loc, starts, q.dtype)
    b, n, s, _ = q.shape
    return (o.reshape(b, n, s, -1).astype(v.dtype),
            (q, k, v, g, beta, starts))


def _delta_rule_bwd(chunk, res, do):
    q, k, v, g, beta, starts = res
    dtype = q.dtype
    b, n, s, _ = q.shape
    loc, pull_locals = jax.vjp(
        lambda *x: _locals(*x, chunk), q, k, v, g, beta)
    do = do.astype(jnp.float32).reshape(b, n, s // chunk, chunk, -1)
    _, pull_outputs = jax.vjp(
        lambda loc, starts: _outputs(loc, starts, dtype), loc, starts)
    d_loc, d_starts = pull_outputs(do)

    # the recurrence's transpose: from the last chunk to the first,
    # carrying the cotangent of the state a chunk leaves
    def chunk_back(d_left, x):
        state, d_start, *locals_ = x
        _, pull = jax.vjp(
            lambda state, *l: _advance(state, *l, dtype), state, *locals_)
        d_state, *d_locals = pull(d_left)
        return d_state + d_start, tuple(d_locals)

    _, d_scan = lax.scan(
        chunk_back, jnp.zeros_like(starts[:, :, 0]),
        (jnp.moveaxis(starts, 2, 0), jnp.moveaxis(d_starts, 2, 0),
         *_per_chunk(loc)), reverse=True)
    d_decay, d_kd, d_w, d_u = (jnp.moveaxis(x, 0, 2) for x in d_scan)
    d_loc = d_loc._replace(decay=d_loc.decay + d_decay, kd=d_loc.kd + d_kd,
                           w=d_loc.w + d_w, u=d_loc.u + d_u)
    return pull_locals(d_loc)


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: Optional[int] = None,
                     heads_a_pass: Optional[int] = None):
    """o (B, n, S, d_v) of the gated delta rule over head-major q, k
    (B, n, S, d_k), v (B, n, S, d_v), the log-decay g (B, n, S, d_k),
    <= 0, and beta (B, n, S); every head starts from a zero state.  o
    has v's dtype; g and beta are best handed over in float32.

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
    need not hold together (a tuned config's `heads`; None is every
    head in one call)."""
    b, n, s, dk = q.shape
    if k.shape != q.shape or g.shape != q.shape or v.shape[:3] != (b, n, s) \
            or beta.shape != (b, n, s):
        raise ValueError(
            f"shapes q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, "
            f"beta {beta.shape} are not (B, n, S, d_k) x 2, (B, n, S, "
            "d_v), (B, n, S, d_k), (B, n, S)")
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
    _calls["calls"] += 1
    _calls["chunk"] = chunk
    _calls["saved_state_bytes"] += 4 * b * n * (s // chunk) * dk * v.shape[-1]
    if heads_a_pass in (None, n):
        return _delta_rule(q, k, v, g, beta, chunk)
    if heads_a_pass < 1 or n % heads_a_pass:
        raise ValueError(f"heads_a_pass {heads_a_pass} does not divide the "
                         f"{n} heads")

    # one call a pass, written out: a loop instruction around them
    # would hide its body from a reader of the trace and carry its
    # operands through copies
    return jnp.concatenate([
        _delta_rule(*(x[:, h:h + heads_a_pass] for x in (q, k, v, g, beta)),
                    chunk)
        for h in range(0, n, heads_a_pass)], axis=1)
