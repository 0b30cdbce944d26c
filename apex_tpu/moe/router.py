"""Top-k expert router — fp32 gates, capacity-aware destinations.

The routing contract (docs/moe.md):

* **fp32 gate logits regardless of compute dtype.**  The gate GEMM
  runs the activations in their compute dtype but accumulates into
  fp32 (`preferred_element_type`) — the logits, softmax, and top-k
  selection are all fp32.  A bf16 softmax loses ties and the tiny
  probability gaps the selection keys on (lint rule DP105 makes a
  low-precision selection a finding).
* **Ties pinned by index.**  `lax.top_k` is stable: equal
  probabilities resolve to the LOWER expert index, so routing is a
  pure function of the logits with no backend-dependent tie noise.
* **Byte-identical blocked path.**  Softmax and top-k are
  row-independent, so chunking the token rows changes scheduling
  only, never values.  `topk_gates` consults the `moe_router` tuner
  op (apex_tpu.tune) for a `block_rows` config; on a miss — every
  untuned machine — the dense single-shot reference runs, which is
  the pre-tuner kernel exactly (the tune/ contract).

The capacity math (`expert_capacity`) and the position-within-expert
assignment (`capacity_destinations`) live here too: together they make
routing emit a STATIC-shaped destination map — tokens beyond an
expert's capacity route to the trash row (index `n_experts *
capacity`), mirroring the KV trash-page trick of apex_tpu.serve, so
compiled shapes never depend on where tokens actually went.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax


def expert_capacity(tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Per-expert, per-source-shard slot count (static).

    ceil(tokens * top_k * capacity_factor / n_experts), rounded up to
    the fp32 sublane (8) and clamped to `tokens` (one expert can never
    receive more than every token once — top-k picks DISTINCT
    experts).  capacity_factor=inf is the no-drop setting: exactly
    `tokens` slots per expert.  Under expert parallelism each expert's
    total capacity is ep * this value (one block per source shard);
    the drop decision stays LOCAL to the source shard, the GShard
    per-group capacity rule.
    """
    if tokens < 1:
        raise ValueError(f"tokens must be >= 1, got {tokens}")
    if math.isinf(capacity_factor):
        return tokens
    if capacity_factor <= 0:
        raise ValueError(
            f"capacity_factor must be > 0 (or inf), got {capacity_factor}")
    c = math.ceil(tokens * top_k * capacity_factor / n_experts)
    c = ((c + 7) // 8) * 8
    return min(c, tokens)


def gate_logits(x, wg) -> jnp.ndarray:
    """fp32 gate logits (T, E) for activations x (T, H) in ANY compute
    dtype: the GEMM keeps low-precision operands (full MXU rate, no
    DP101 upcast) and accumulates fp32 — the output IS fp32, never a
    downcast-then-upcast round trip."""
    return jnp.dot(x, wg.astype(x.dtype),
                   preferred_element_type=jnp.float32)


def _softmax_topk(logits, top_k: int):
    probs = jax.nn.softmax(logits, axis=-1)          # fp32
    gate, idx = lax.top_k(probs, top_k)              # ties -> low index
    return probs, gate, idx


class RouterOutput(NamedTuple):
    """Everything downstream dispatch/combine and the aux losses need.

    probs: (T, E) fp32 full softmax; gate: (T, k) fp32 selected probs
    (RAW, not renormalized — Switch-style, so the router receives main
    -loss gradient at any k; at k=1/E=1 the gate is exactly 1.0, the
    dense-parity anchor); idx: (T, k) int32 expert ids; logits: (T, E)
    fp32 (the z-loss reads these)."""

    probs: jnp.ndarray
    gate: jnp.ndarray
    idx: jnp.ndarray
    logits: jnp.ndarray


def topk_gates_dense(x, wg, top_k: int) -> RouterOutput:
    """The dense reference: one softmax + top_k over all token rows."""
    logits = gate_logits(x, wg)
    probs, gate, idx = _softmax_topk(logits, top_k)
    return RouterOutput(probs=probs, gate=gate, idx=idx, logits=logits)


def topk_gates_blocked(x, wg, top_k: int, block_rows: int) -> RouterOutput:
    """Row-blocked path: the same softmax + top_k over `block_rows`-row
    chunks via lax.map.  Byte-identical to the dense reference (both
    ops are row-independent); the block size only moves the
    VMEM-residency / grid-overhead point on TPU."""
    logits = gate_logits(x, wg)
    t = logits.shape[0]
    pad = (-t) % block_rows
    padded = jnp.pad(logits, ((0, pad), (0, 0)))
    blocks = padded.reshape(-1, block_rows, logits.shape[1])
    probs_b, gate_b, idx_b = lax.map(
        lambda b: _softmax_topk(b, top_k), blocks)
    e = logits.shape[1]
    return RouterOutput(
        probs=probs_b.reshape(-1, e)[:t],
        gate=gate_b.reshape(-1, top_k)[:t],
        idx=idx_b.reshape(-1, top_k)[:t],
        logits=logits)


def topk_gates(x, wg, top_k: int,
               block_rows: Optional[int] = None) -> RouterOutput:
    """Route x (T, H) through gate weight wg (H, E): the `moe_router`
    tuner op.  An explicit `block_rows` wins; otherwise the tune cache
    is consulted at trace time (host-side dict access, zero device
    work) and a miss falls back to the dense reference — byte-identical
    on every path, per the tune/ contract."""
    if block_rows is None:
        from apex_tpu import tune

        cfg = tune.tuned("moe_router", tune.moe_router_attrs(
            x.shape[0], wg.shape[1], top_k, x.dtype))
        if cfg:
            blk = cfg.get("block_rows")
            if isinstance(blk, int) and 8 <= blk <= 1 << 16 \
                    and blk % 8 == 0:
                block_rows = blk
    if block_rows is None:
        return topk_gates_dense(x, wg, top_k)
    return topk_gates_blocked(x, wg, top_k, block_rows)


class SigmoidRouterOutput(NamedTuple):
    """scores: (T, E) fp32 sigmoid affinities of every expert; idx:
    (T, k) int32 chosen experts, the highest biased score first;
    weight: (T, k) fp32, what each chosen expert's output is scaled
    by."""

    scores: jnp.ndarray
    idx: jnp.ndarray
    weight: jnp.ndarray


def sigmoid_topk_gates(x, wr, bias, top_k: int, *, scale: float = 1.0,
                       renormalize: bool = True) -> SigmoidRouterOutput:
    """The bias-corrected sigmoid gate (DeepSeek-V3's `noaux_tc` with
    one group): scores `s = sigmoid(x @ wr)` in fp32; the `top_k`
    experts of `s + bias` are chosen, and the bias has no further
    part: the weights are the chosen experts' own scores, divided by
    their sum (`renormalize`) and multiplied by `scale`.

    `bias` (E,) steers load and is not trained by the loss: it is
    taken under `stop_gradient`.  Ties go to the lower index, as in
    `topk_gates`."""
    scores = jax.nn.sigmoid(gate_logits(x, wr))               # (T, E) fp32
    biased = scores + lax.stop_gradient(bias.astype(jnp.float32))
    _, idx = lax.top_k(biased, top_k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalize:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return SigmoidRouterOutput(scores=scores, idx=idx,
                               weight=weight * scale)


def capacity_destinations(idx, n_experts: int, capacity: int):
    """Flat destination rows for each (token, slot) assignment.

    idx: (T, k) int32 expert choices.  Returns (dest, n_dropped):
    dest (T, k) int32 into a flat (n_experts * capacity + 1)-row
    buffer — assignment j of token t lands at `expert * capacity +
    position` where position counts earlier assignments of the same
    expert (slot-major priority: all slot-0 choices outrank slot-1),
    or at the TRASH row (`n_experts * capacity`) once the expert's
    local capacity is full.  n_dropped is the per-expert (E,) fp32
    dropped-assignment count.  Shapes are static — routing can never
    cause a recompile."""
    t, k = idx.shape
    dests = []
    counts = jnp.zeros((n_experts,), jnp.int32)
    dropped = jnp.zeros((n_experts,), jnp.float32)
    for j in range(k):
        oh = jax.nn.one_hot(idx[:, j], n_experts, dtype=jnp.int32)
        pos_table = counts[None, :] + jnp.cumsum(oh, axis=0) - oh
        pos = jnp.sum(oh * pos_table, axis=1)            # (T,)
        keep = pos < capacity
        dests.append(jnp.where(keep, idx[:, j] * capacity + pos,
                               n_experts * capacity))
        counts = counts + jnp.sum(oh, axis=0)
        dropped = dropped + jnp.sum(
            jnp.where(keep[:, None], 0, oh).astype(jnp.float32), axis=0)
    return jnp.stack(dests, axis=1), dropped


def load_balancing_aux(probs, idx, n_experts: int):
    """The Switch/GShard load-balancing auxiliary loss and its stats.

    f_e = fraction of (token, slot) assignments routed to expert e
    (hard counts, piecewise-constant — gradient flows through P_e
    only); P_e = mean gate probability of e.  aux = E * sum(f * P):
    1.0 at perfect balance, larger when load concentrates.  Returns
    (aux fp32 scalar, f (E,) fp32, P (E,) fp32)."""
    t, k = idx.shape
    assign = jnp.zeros((n_experts,), jnp.float32)
    for j in range(k):
        assign = assign + jnp.sum(
            jax.nn.one_hot(idx[:, j], n_experts, dtype=jnp.float32),
            axis=0)
    f = assign / jnp.asarray(t * k, jnp.float32)
    p_mean = jnp.mean(probs, axis=0)
    aux = jnp.asarray(n_experts, jnp.float32) * jnp.sum(f * p_mean)
    return aux, f, p_mean


def router_z_loss(logits):
    """mean(logsumexp(logits)^2) — keeps gate logits from drifting to
    magnitudes where the fp32 softmax itself saturates (ST-MoE)."""
    return jnp.mean(jnp.square(
        jax.scipy.special.logsumexp(logits, axis=-1)))


def gate_entropy(probs):
    """Per-token gate entropy (T,) fp32 — the collapse detector the
    `block{i}/moe/gate_entropy` tap carries (mean -> average entropy;
    near-zero mean means the router collapsed to single experts)."""
    plogp = jnp.where(probs > 0,
                      probs * jnp.log(jnp.maximum(probs, 1e-30)), 0.0)
    return -jnp.sum(plogp, axis=-1)
