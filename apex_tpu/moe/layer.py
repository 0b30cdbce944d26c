"""`MoEMLP` — the expert-parallel drop-in for a transformer block's MLP.

Composition of the subsystem's three pieces (router.py, dispatch.py)
into one shard-local layer that runs inside `shard_map` over the
(pp, dp[, ep], tp) mesh:

    route (fp32 gates) -> dispatch into (E, C, H) -> all_to_all over ep
    -> per-expert FFN (bf16-friendly, fp32 MXU accumulation)
    -> all_to_all back -> combine weighted by raw gate probs

Parameter layout: every shard holds the FULL (E, ...) expert tensors —
the ZeRO-2 posture: compute-time replicated, master state sharded over
the combined (dp, ep) axes by `DistributedFusedAdam(num_shards=dp*ep,
axis_name=("dp","ep"), ep_shards=ep)` — and slices its own E/ep
experts by `lax.axis_index("ep")` at compute time.  Gradient
correctness needs NO expert-special sync: the combine all_to_all's AD
transpose routes each shard's loss cotangents back to the shard that
computed the expert, so after backward every shard already holds
d(sum of its ep group's losses)/d(its expert slice) — a uniform pmean
over ("dp", "ep") is then exact for expert and non-expert params
alike (docs/moe.md derives this).

Telemetry: when a flight-recorder TapContext is armed, the layer taps
`{prefix}/load` (per-expert assignment fractions — absmax = hottest
expert), `{prefix}/drop` (per-expert dropped fractions — mean = drop
fraction) and `{prefix}/gate_entropy` (per-token gate entropy — mean
falling toward 0 = router collapse) through the existing TapState
plane: zero host syncs, zero collectives, and the untapped program is
byte-identical because the whole hook is trace-time gated on
`active_tap_context()`.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from apex_tpu.moe import dispatch as D
from apex_tpu.moe import router as R
from apex_tpu.ops._common import active_tap_context, tap as _tap
from apex_tpu.parallel.collectives import (
    copy_to_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
)
from apex_tpu.parallel.mesh import EP_AXIS


class MoEAux(NamedTuple):
    """Per-layer fp32 scalars the model folds into its loss/stats."""

    aux_loss: jnp.ndarray        # load-balancing loss (1.0 = balanced)
    z_loss: jnp.ndarray          # router z-loss
    drop_fraction: jnp.ndarray   # dropped assignments / (T * k)
    gate_entropy: jnp.ndarray    # mean per-token gate entropy


class MoEMLP:
    """Expert MLP bank: E experts of (H -> ffn_mult*H -> H), gelu.

    Drop-in for the GPT block's ColumnParallel->gelu->RowParallel MLP:
    at n_experts=1 / top_k=1 / capacity_factor=inf the output is
    BITWISE the dense MLP's (same GEMM contractions row-for-row, gate
    exactly 1.0) — the acceptance anchor tests/test_moe.py pins.
    """

    def __init__(self, hidden: int, ffn_hidden: int, n_experts: int, *,
                 top_k: int = 1, capacity_factor: float = 1.25,
                 ep_size: int = 1, ep_axis: str = EP_AXIS,
                 init_std: float = 0.02,
                 proj_init_std: Optional[float] = None,
                 router_block_rows: Optional[int] = None,
                 tp_axis: Optional[str] = None,
                 overlap_chunks=None):
        if n_experts % max(1, ep_size):
            raise ValueError(
                f"n_experts={n_experts} must divide by ep_size={ep_size}")
        if top_k > n_experts:
            raise ValueError(f"top_k={top_k} > n_experts={n_experts}")
        self.hidden = hidden
        self.ffn_hidden = ffn_hidden
        self.n_experts = n_experts
        self.top_k = top_k
        self.capacity_factor = float(capacity_factor)
        self.ep_size = ep_size
        self.ep_axis = ep_axis
        self.init_std = init_std
        self.proj_init_std = proj_init_std or init_std
        self.router_block_rows = router_block_rows
        # tp_axis: the dense GPT block's tensor-parallel region markers
        # (ColumnParallel's copy_to on entry, RowParallel's reduce_from
        # before the output bias), mirrored here so the drop-in keeps
        # the identical op sequence.  MoE experts REPLICATE over tp —
        # only tp == 1 is supported (the markers are then identities;
        # at tp > 1 the duplicate-compute reduce would scale outputs
        # by tp, so apply() raises at trace time when the bound tp
        # axis has size > 1).
        self.tp_axis = tp_axis
        # micro-chunk depth of the dispatch/combine exchange
        # (dispatch.chunked_expert_exchange): None = tuner-owned
        # (`overlap_chunks` op, heuristic 1 = the monolithic exchange,
        # byte-identical); an int forces it for A/B sweeps.
        self.overlap_chunks = overlap_chunks

    # ------------------------------ params --------------------------------

    def init(self, key, dtype=jnp.float32) -> dict:
        kg, k1, k2 = jax.random.split(key, 3)
        e, h, f = self.n_experts, self.hidden, self.ffn_hidden
        return {
            "wg": jax.random.normal(kg, (h, e), dtype) * self.init_std,
            "w1": jax.random.normal(k1, (e, h, f), dtype) * self.init_std,
            "b1": jnp.zeros((e, f), dtype),
            "w2": jax.random.normal(k2, (e, f, h), dtype)
            * self.proj_init_std,
            "b2": jnp.zeros((e, h), dtype),
        }

    def partition_specs(self) -> dict:
        """Everything REPLICATED — the compute-time contract: every
        shard holds the full (E, ...) expert tensors (the ZeRO-2
        posture; `_local_experts` slices this shard's E/ep experts by
        axis_index at compute time, which requires the full tensor as
        input).  Ep-RESIDENT expert params — P(ep, ...) leaves with no
        gather — are the ZeRO-3 rung of ROADMAP item 1, and would
        change `_local_experts` in the same commit as this spec."""
        return {"wg": P(), "w1": P(), "b1": P(), "w2": P(), "b2": P()}

    # ------------------------------ forward -------------------------------

    def _local_experts(self, params):
        """This shard's E/ep slice of each expert tensor (the whole
        tensor when ep_size == 1 — no axis_index traced)."""
        if self.ep_size == 1:
            return (params["w1"], params["b1"], params["w2"], params["b2"])
        e_loc = self.n_experts // self.ep_size
        start = lax.axis_index(self.ep_axis) * e_loc

        def sl(a):
            return lax.dynamic_slice_in_dim(a, start, e_loc, axis=0)

        return (sl(params["w1"]), sl(params["b1"]),
                sl(params["w2"]), sl(params["b2"]))

    def _expert_ffn(self, params, xe, cn=None):
        """Per-expert FFN on the exchanged buffer (E_loc, rows, H):
        the same dot/astype/bias/gelu sequence as the dense
        ColumnParallel -> gelu -> RowParallel pair, batched over the
        expert dim with fp32 MXU accumulation.  cn: optional pair of
        checkpoint_name tags applied where the dense GPT block tags
        its MLP (after each projection+bias) — the model passes
        ("ffn1", "ffn_out") so remat policies keep addressing the
        same points."""
        w1, b1, w2, b2 = self._local_experts(params)
        h = jnp.einsum("ech,ehf->ecf", xe, w1,
                       preferred_element_type=jnp.float32).astype(xe.dtype)
        h = h + b1[:, None, :].astype(h.dtype)
        if cn:
            h = checkpoint_name(h, cn[0])
        h = jax.nn.gelu(h, approximate=True)
        y = jnp.einsum("ecf,efh->ech", h, w2,
                       preferred_element_type=jnp.float32).astype(h.dtype)
        if self.tp_axis is not None:
            y = reduce_from_tensor_model_parallel_region(y, self.tp_axis)
        y = y + b2[:, None, :].astype(y.dtype)
        if cn:
            y = checkpoint_name(y, cn[1])
        return y

    def _exchange_chunks(self, capacity: int, dtype) -> int:
        """Trace-time micro-chunk count for the ep exchange: explicit
        override, else the `overlap_chunks` tuner op (heuristic 1 on a
        miss).  Non-dividing requests fall back to the largest divisor
        of the capacity, warn once (the flash-attention block rule)."""
        from apex_tpu.parallel import overlap as OV
        req = self.overlap_chunks
        if req is None:
            from apex_tpu import tune
            cfg = tune.tuned("overlap_chunks", tune.overlap_attrs(
                "moe", capacity, self.hidden, self.ep_size, dtype))
            req = int(cfg["chunks"]) if cfg else 1
        req = int(req)
        if req <= 1:
            return 1
        return OV.resolve_chunks(req, capacity, site="moe")

    def apply(self, params, x, tap_prefix: Optional[str] = None,
              cn=None):
        """x: (..., H) shard-local activations (any leading dims —
        (S, B, H) from a GPT block).  Returns (y, MoEAux) with y in
        x's shape and dtype.  Call inside shard_map when ep_size > 1
        (the all_to_all needs the bound ep axis).  cn: checkpoint_name
        tag pair, see _expert_ffn."""
        lead_shape = x.shape[:-1]
        if self.tp_axis is not None:
            try:
                tp = int(lax.axis_size(self.tp_axis))
            except NameError:  # axis unbound (outside shard_map)
                tp = 1
            if tp > 1:
                # loud error, not silent wrongness: experts REPLICATE
                # over tp, so the duplicate-compute reduce_from below
                # would scale every MoE output by tp
                raise NotImplementedError(
                    f"MoEMLP does not support tensor parallelism yet "
                    f"(tp axis {self.tp_axis!r} has size {tp}): experts "
                    "replicate over tp and the RowParallel-style "
                    "reduction would multiply outputs by tp — build "
                    "the MoE mesh with tensor_model_parallel_size=1")
            # the dense ColumnParallel entry marker (identity forward,
            # grad psum over tp in backward) — see __init__
            x = copy_to_tensor_model_parallel_region(x, self.tp_axis)
        xt = x.reshape(-1, self.hidden)
        t = xt.shape[0]
        e, k = self.n_experts, self.top_k
        cap = R.expert_capacity(t, e, k, self.capacity_factor)

        out = R.topk_gates(xt, params["wg"], k,
                           block_rows=self.router_block_rows)
        if e == 1 and k == 1 and cap >= t and self.ep_size == 1:
            # Degenerate routing (the n_experts=1 limit): every token
            # goes to expert 0 with gate exactly 1.0 and the dispatch
            # permutation is the identity, so the scatter/exchange/
            # gather collapses away and the expert FFN runs on the
            # ORIGINAL activation shape — a real optimization (no
            # buffers, no scatter) that also makes this limit BITWISE
            # the dense MLP: same op shapes means XLA fuses the bias-
            # grad reductions identically (the general (E, C, H) path
            # is bitwise in VALUES but fuses those reduces in a
            # different loop order).  Dispatch itself is covered by
            # the round-trip and dp x ep grid tests.
            dropped = jnp.zeros((1,), jnp.float32)
            y1 = jnp.dot(x, params["w1"][0],
                         preferred_element_type=jnp.float32
                         ).astype(x.dtype)
            y1 = y1 + params["b1"][0].astype(y1.dtype)
            if cn:
                y1 = checkpoint_name(y1, cn[0])
            y1 = jax.nn.gelu(y1, approximate=True)
            y2 = jnp.dot(y1, params["w2"][0],
                         preferred_element_type=jnp.float32
                         ).astype(y1.dtype)
            if self.tp_axis is not None:
                y2 = reduce_from_tensor_model_parallel_region(
                    y2, self.tp_axis)
            # softmax over ONE logit is identically the constant 1.0,
            # so the gate weighting is the identity FUNCTION (value
            # and derivative) — skipping the multiply is exact, and
            # keeps the router's ops out of the MLP's forward/backward
            # fusion neighborhoods (an extra *1.0 changes nothing in
            # values but re-tiles the layernorm-backward reduce, an
            # accumulation-order wobble that would break the bitwise
            # anchor).  The router still runs for gates/aux stats.
            y2 = y2 + params["b2"][0].astype(y2.dtype)
            if cn:
                y2 = checkpoint_name(y2, cn[1])
            y = y2.reshape(-1, self.hidden)
        else:
            dest, dropped = R.capacity_destinations(out.idx, e, cap)
            buf = D.dispatch(xt, dest, e, cap)
            # micro-chunked exchange (ISSUE 18): chunk k+1's dispatch
            # all_to_all overlaps chunk k's expert FFN; chunks == 1 is
            # the monolithic sequence, byte-identical
            chunks = self._exchange_chunks(cap, xt.dtype)
            ybuf = D.chunked_expert_exchange(
                buf, lambda xe: self._expert_ffn(params, xe, cn=cn),
                self.ep_axis, self.ep_size, e, cap, chunks)
            y = D.combine(ybuf, dest, out.gate)

        aux_loss, load, _ = R.load_balancing_aux(out.probs, out.idx, e)
        drop_per_expert = dropped / jnp.asarray(t * k, jnp.float32)
        ent = R.gate_entropy(out.probs)
        aux = MoEAux(aux_loss=aux_loss,
                     z_loss=R.router_z_loss(out.logits),
                     drop_fraction=jnp.sum(drop_per_expert),
                     gate_entropy=jnp.mean(ent))

        if tap_prefix is not None and active_tap_context() is not None:
            # flight-recorder hook, armed at TRACE time only: the
            # tapped stat tensors ride into the loss through a 0.0 *
            # sum so AD's probe-cotangent path runs for them (the fwd
            # stats plane is a residual — a zero cotangent still emits
            # it); untapped traces skip this block entirely, keeping
            # the byte-identical contract of ops._common.tap
            s = (_tap(load, f"{tap_prefix}/load").sum()
                 + _tap(drop_per_expert, f"{tap_prefix}/drop").sum()
                 + _tap(ent, f"{tap_prefix}/gate_entropy").sum())
            y = y + (0.0 * s).astype(y.dtype)

        return y.reshape(*lead_shape, self.hidden), aux


class HeldExpertsStats(NamedTuple):
    """What one call of `HeldExpertsMLP.apply` counted.  counts:
    (count,) int32 assignments each held expert was sent (what a
    router-bias update reads); overflow: int32 assignments to held
    experts beyond the grouped buffer's row bound, which were left
    out (0 in a sound run)."""

    counts: jnp.ndarray
    overflow: jnp.ndarray


def swiglu(x, w_gate_up, w_down):
    """(silu(x @ W_g) * (x @ W_u)) @ W_d with `w_gate_up` = [W_g | W_u]
    side by side, (H, 2F): bf16-friendly operands, fp32 accumulation,
    no biases."""
    gu = jnp.dot(x, w_gate_up,
                 preferred_element_type=jnp.float32).astype(x.dtype)
    gate, up = jnp.split(gu, 2, axis=-1)
    return jnp.dot(jax.nn.silu(gate) * up, w_down,
                   preferred_element_type=jnp.float32).astype(x.dtype)


class HeldExpertsMLP:
    """The share of a fine-grained MoE layer that one chip of an
    expert-parallel group holds: experts `[first, first + count)` of
    `n_experts`, and the shared expert every chip computes alike.

    The router keeps its whole width: every token chooses `top_k` of
    all `n_experts` by the bias-corrected sigmoid gate
    (`router.sigmoid_topk_gates`).  The layer has parameters for its
    own experts only and computes their part of the result; what the
    experts held elsewhere would add is left out (under expert
    parallelism their chips add it, through an exchange this layer
    does not have).  The assignments to held experts are sorted by
    expert (`dispatch.group_by_expert`), their tokens gathered into
    one (rows, H) buffer, and every expert's SwiGLU runs over its own
    run of rows as a grouped GEMM (`jax.lax.ragged_dot`, which the TPU
    compiler turns into a grouped-matmul kernel of its own).  There is
    no per-expert capacity and no dropped token: `rows` is a static
    bound on the total, `rows_factor` times (twice, by default) what
    uniform routing sends (`rows_bound`), and
    `HeldExpertsStats.overflow` counts what would not fit.

    Experts are SwiGLUs without biases, gate and up projection side by
    side in one tensor: `experts_gate_up` (count, H, 2F),
    `experts_down` (count, F, H); the shared expert's `shared_gate_up`
    (H, 2F'), `shared_down` (F', H) with F' = n_shared * F; `router`
    (H, n_experts) and `router_bias` (n_experts,), which steers the
    choice only and gets no gradient."""

    def __init__(self, hidden: int, ffn_hidden: int, n_experts: int, *,
                 first: int, count: int, top_k: int, n_shared: int = 1,
                 scale: float = 1.0, renormalize: bool = True,
                 init_std: float = 0.02, bias_range: float = 0.0,
                 rows_factor: float = 2.0):
        if not 0 <= first <= first + count <= n_experts:
            raise ValueError(
                f"held experts [{first}, {first + count}) are not "
                f"within the {n_experts} routed")
        if top_k > n_experts:
            raise ValueError(f"top_k={top_k} > n_experts={n_experts}")
        self.hidden, self.ffn_hidden = hidden, ffn_hidden
        self.n_experts, self.first, self.count = n_experts, first, count
        self.top_k, self.n_shared = top_k, n_shared
        self.scale, self.renormalize = scale, renormalize
        self.init_std, self.bias_range = init_std, bias_range
        self.rows_factor = rows_factor

    def rows_bound(self, tokens: int) -> int:
        """Rows of the grouped buffer: `rows_factor` times (twice,
        unless the caller knows its share swings wider) the assignments
        uniform routing sends to the held experts, rounded up to the
        sublane tile, and never more than every assignment."""
        expected = tokens * self.top_k * self.count / self.n_experts
        return min(tokens * self.top_k,
                   -(-int(self.rows_factor * expected) // 8) * 8)

    def init(self, key, dtype=jnp.float32) -> dict:
        ks = jax.random.split(key, 6)
        h, f, n = self.hidden, self.ffn_hidden, self.count

        def normal(k, shape):
            return jax.random.normal(k, shape, dtype) * self.init_std

        params = {
            "router": normal(ks[0], (h, self.n_experts)),
            "router_bias": jax.random.uniform(
                ks[1], (self.n_experts,), dtype, -1.0, 1.0)
            * self.bias_range,
            "experts_gate_up": normal(ks[2], (n, h, 2 * f)),
            "experts_down": normal(ks[3], (n, f, h)),
        }
        if self.n_shared:
            params["shared_gate_up"] = normal(
                ks[4], (h, 2 * self.n_shared * f))
            params["shared_down"] = normal(ks[5], (self.n_shared * f, h))
        return params

    def partition_specs(self) -> dict:
        """Replicated over the mesh's axes: the layer's parameters are
        already one chip's share, told by `first` and `count`."""
        names = ["router", "router_bias", "experts_gate_up", "experts_down"]
        if self.n_shared:
            names += ["shared_gate_up", "shared_down"]
        return {name: P() for name in names}

    def apply(self, params, x):
        """x: (..., H).  Returns (y, HeldExpertsStats), y in x's shape
        and dtype: the held experts' weighted outputs plus the shared
        expert's."""
        lead = x.shape[:-1]
        xt = x.reshape(-1, self.hidden)
        t = xt.shape[0]
        with jax.named_scope("router"):
            gates = R.sigmoid_topk_gates(
                xt, params["router"], params["router_bias"], self.top_k,
                scale=self.scale, renormalize=self.renormalize)
        with jax.named_scope("dispatch"):
            groups = D.group_by_expert(gates.idx, self.first, self.count,
                                       self.rows_bound(t))
            xg = D.gather_groups(xt, groups)
        # Rows past the last group belong to no expert.  A grouped GEMM
        # leaves them as it finds them (on the chip: whatever the buffer
        # held, forward and backward alike), so they are zeroed where
        # they enter and where they leave each product: no such row's
        # value or cotangent reaches a token or a weight.
        rows = groups.valid[:, None]
        with jax.named_scope("experts"):
            gu = lax.ragged_dot(
                jnp.where(rows, xg, 0), params["experts_gate_up"],
                groups.sizes,
                preferred_element_type=jnp.float32).astype(xt.dtype)
            gate, up = jnp.split(gu, 2, axis=-1)
            yg = lax.ragged_dot(
                jnp.where(rows, jax.nn.silu(gate) * up, 0),
                params["experts_down"], groups.sizes,
                preferred_element_type=jnp.float32)
        with jax.named_scope("combine"):
            y = D.scatter_groups(yg, gates.weight, groups, t)
        if self.n_shared:
            with jax.named_scope("shared"):
                y = y + swiglu(xt, params["shared_gate_up"],
                               params["shared_down"])
        stats = HeldExpertsStats(counts=groups.counts,
                                 overflow=groups.overflow)
        return y.astype(x.dtype).reshape(*lead, self.hidden), stats


def mean_aux(auxes) -> MoEAux:
    """Average a list of per-layer MoEAux into one (fp32 scalars)."""
    n = jnp.asarray(len(auxes), jnp.float32)
    return MoEAux(*[
        sum(getattr(a, f) for a in auxes) / n for f in MoEAux._fields])
