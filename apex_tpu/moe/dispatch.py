"""Dense dispatch/combine + the ep all-to-all exchange.

The sparse-accumulation lesson of arXiv 1905.04035 applied to expert
parallelism: **densify before the collective, never ship ragged sparse
payloads**.  Tokens scatter into a fixed `(n_experts, capacity,
d_model)` buffer (dropped tokens go to a trash row that stays local,
so the exchanged payload's shape depends on NOTHING the router
decided), and the whole cross-expert exchange is ONE tiled
`all_to_all` over the `ep` mesh axis each way:

    dispatch:  (E, C, H) --all_to_all(split 0, concat 1)--> (E/ep, ep*C, H)
    combine:   (E/ep, ep*C, H) --all_to_all(split 1, concat 0)--> (E, C, H)

Each shard dispatches its LOCAL tokens into slots for ALL E global
experts; the exchange hands every ep peer the block for the experts it
owns and returns the computed outputs the same way.  The payload is
E*C*H * itemsize bytes per direction, priced by the ICI roofline's
ring all-to-all formula ((n-1)/n * D / bw, monitor/comms/roofline.py)
and inventoried by the comms gate (`comms_probe.py moe`).

Scatter/gather discipline: every non-trash destination row is unique
by construction (positions within an expert are distinct across all
(token, slot) assignments), so the scatter is exact — a kept token's
row is its activation bit-for-bit, which is what makes the
capacity_factor=inf round trip and the n_experts=1 dense-GPT parity
BITWISE, not just close.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class ExpertGroups(NamedTuple):
    """The assignments to a run of held experts, sorted by expert.

    token, slot: (rows,) int32, the token and the top-k slot of each
    grouped row; sizes: (count,) int32 rows of each held expert, in
    expert order (what a grouped GEMM takes); valid: (rows,) bool, the
    rows that hold an assignment (the first `sum(sizes)`); counts:
    (count,) int32 assignments each held expert was sent, bound or no
    bound; overflow: int32 assignments to held experts that found no
    row."""

    token: jnp.ndarray
    slot: jnp.ndarray
    sizes: jnp.ndarray
    valid: jnp.ndarray
    counts: jnp.ndarray
    overflow: jnp.ndarray


def group_by_expert(idx, first: int, count: int, rows: int) -> ExpertGroups:
    """Sort the (token, slot) assignments `idx` (T, k) to experts
    `[first, first + count)` by expert, into at most `rows` rows.

    One stable sort of the T*k assignments: those to held experts come
    first, by expert and within an expert by token; those to experts
    held elsewhere sort behind them and are left out.  There is no
    per-expert capacity: `rows` bounds the total, and an expert may
    take any share of it.  Assignments beyond the bound fall off the
    end of the sorted order (the last experts' last tokens) and are
    counted in `overflow`."""
    t, k = idx.shape
    local = idx.reshape(-1).astype(jnp.int32) - first
    # the sort key: the held experts 0..count-1, everything else `count`
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True)[:rows]
    counts = jnp.zeros((count,), jnp.int32).at[key].add(1, mode="drop")
    ends = jnp.minimum(jnp.cumsum(counts), rows)
    sizes = jnp.diff(ends, prepend=0)
    valid = jnp.arange(order.shape[0]) < ends[-1]
    return ExpertGroups(token=order // k, slot=order % k, sizes=sizes,
                        valid=valid, counts=counts,
                        overflow=jnp.sum(counts) - ends[-1])


def gather_groups(x, groups: ExpertGroups) -> jnp.ndarray:
    """x (T, H) -> (rows, H): every grouped row's token.  Rows that
    hold no assignment repeat some token; nothing reads them."""
    return jnp.take(x, groups.token, axis=0)


def scatter_groups(y, weight, groups: ExpertGroups, tokens: int):
    """The inverse of `gather_groups`, weighted: (rows, H) expert
    outputs -> (T, H), each row times its assignment's `weight` (T, k)
    added to its token, in fp32.  Rows that hold no assignment add
    nothing."""
    w = jnp.where(groups.valid, weight[groups.token, groups.slot], 0.0)
    y = jnp.where(groups.valid[:, None], y.astype(jnp.float32), 0.0)
    return jnp.zeros((tokens, y.shape[1]), jnp.float32).at[
        groups.token].add(y * w[:, None])


def dispatch(x, dest, n_experts: int, capacity: int) -> jnp.ndarray:
    """Scatter token rows x (T, H) to their destination slots.

    dest: (T, k) flat rows from `router.capacity_destinations`.
    Returns the dense (E*C + 1, H) buffer in x's dtype — row E*C is
    the trash row (dropped tokens pile up there and are never read).
    Non-trash rows are unique, so `.set` writes each kept token's
    activation exactly; unfilled slots stay zero and contribute
    nothing downstream (zero rows through the expert MLP produce
    bias-only outputs that combine never reads)."""
    t, h = x.shape
    k = dest.shape[1]
    buf = jnp.zeros((n_experts * capacity + 1, h), x.dtype)
    for j in range(k):
        buf = buf.at[dest[:, j]].set(x)
    return buf


def combine(ybuf, dest, gate) -> jnp.ndarray:
    """Gather expert outputs back to token order, weighted by gates.

    ybuf: (E*C + 1, H) with the trash row ZEROED (exchange_combine
    rebuilds it that way), dest: (T, k), gate: (T, k) fp32 raw gate
    probs.  Dropped assignments index the trash row and contribute
    exactly 0 — a fully dropped token passes through on the residual
    alone.  The weight multiply casts the GATE to the activation
    dtype (not the activations to fp32): at gate == 1.0 the product
    is the expert output bit-for-bit, the dense-parity anchor."""
    k = dest.shape[1]
    out = ybuf[dest[:, 0]] * gate[:, 0, None].astype(ybuf.dtype)
    for j in range(1, k):
        out = out + ybuf[dest[:, j]] * gate[:, j, None].astype(ybuf.dtype)
    return out


def exchange_dispatch(buf, ep_axis, ep_size: int, n_experts: int,
                      capacity: int) -> jnp.ndarray:
    """(E*C+1, H) local dispatch buffer -> (E/ep, ep*C, H) rows for
    THIS shard's experts, gathered from every ep peer.  The trash row
    is sliced off first — it is local-only garbage and shipping it
    would waste ICI bytes for values nobody reads.  ep_size == 1 is
    the degenerate reshape (no collective traced at all)."""
    h = buf.shape[1]
    ebuf = buf[:n_experts * capacity].reshape(n_experts, capacity, h)
    if ep_size == 1:
        return ebuf
    # tiled all_to_all: expert-group chunk g of dim 0 ships to ep peer
    # g; the ep received chunks concatenate along the slot dim
    return lax.all_to_all(ebuf, ep_axis, split_axis=0, concat_axis=1,
                          tiled=True)


def exchange_combine(y, ep_axis, ep_size: int, n_experts: int,
                     capacity: int) -> jnp.ndarray:
    """Inverse exchange + trash-row rebuild: expert outputs
    (E_loc, ep*C, H) -> the (E*C + 1, H) combine buffer in original
    (expert, slot) order with a fresh zero trash row."""
    h = y.shape[-1]
    if ep_size > 1:
        y = lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                           tiled=True)
    flat = y.reshape(n_experts * capacity, h)
    return jnp.concatenate([flat, jnp.zeros((1, h), flat.dtype)], axis=0)


def chunked_expert_exchange(buf, ffn, ep_axis, ep_size: int,
                            n_experts: int, capacity: int,
                            chunks: int = 1) -> jnp.ndarray:
    """dispatch-exchange -> expert FFN -> combine-exchange, micro-
    chunked along the capacity dim (ISSUE 18): the dispatch
    all_to_all of chunk k+1 and the combine all_to_all of chunk k-1
    both ride ICI while the expert FFN chews chunk k.

    `ffn(xe)` maps (E_loc, rows, H) -> (E_loc, rows, H) and must be
    ROW-INDEPENDENT along the slot dim (MoEMLP._expert_ffn is: the
    einsum contracts hidden dims only) — that is what makes each
    chunk's rows bitwise the rows of the monolithic exchange, and the
    concatenation an exact reassembly.  Slot chunk j of every expert
    travels together, so each chunk's exchange is the same tiled
    all_to_all pattern at capacity/chunks rows — chunk-count-many
    smaller collectives, same total bytes (the comms-fixture pin).

    chunks == 1 is EXACTLY the monolithic exchange_dispatch -> ffn ->
    exchange_combine sequence (byte-identical trace, the
    RecompileSentry anchor).  AD needs no custom_vjp: all_to_all
    transposes to its inverse per chunk, and the ffn's parameter
    grads sum across the chunk calls automatically."""
    if chunks <= 1:
        xe = exchange_dispatch(buf, ep_axis, ep_size, n_experts, capacity)
        ye = ffn(xe)
        return exchange_combine(ye, ep_axis, ep_size, n_experts, capacity)
    h = buf.shape[1]
    ebuf = buf[:n_experts * capacity].reshape(n_experts, capacity, h)
    cc = capacity // chunks
    outs = []
    for j in range(chunks):
        piece = lax.slice_in_dim(ebuf, j * cc, (j + 1) * cc, axis=1)
        if ep_size > 1:
            piece = lax.all_to_all(piece, ep_axis, split_axis=0,
                                   concat_axis=1, tiled=True)
        ye = ffn(piece)  # (E_loc, ep*cc, H), rows independent
        if ep_size > 1:
            ye = lax.all_to_all(ye, ep_axis, split_axis=1,
                                concat_axis=0, tiled=True)
        outs.append(ye)
    y = jnp.concatenate(outs, axis=1)  # (E, capacity, H), slot order
    flat = y.reshape(n_experts * capacity, h)
    return jnp.concatenate([flat, jnp.zeros((1, h), flat.dtype)], axis=0)
