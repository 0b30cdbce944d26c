"""HybridMoE — a decoder whose layers mix two kinds of token mixer in
one stack over expert layers, as one chip of an expert-parallel group
holds it: softmax attention without positions on the layers
`attention_layers` names (`attention_kind`: gated grouped-query
attention, or latent attention), Kimi Delta Attention (a gated delta
rule with a per-channel decay, arXiv:2510.26692) on the others.

The layers (no bias but KDA's `dt_bias`, RMSNorm, untied embedding and
head, no rotary embedding and no position table anywhere):

* block: `h = x + Mixer_i(RMSNorm(x))`, `y = h + FFN_i(RMSNorm(h))`,
  the FFN of the leading `first_k_dense_replace` layers a SwiGLU of
  `intermediate_size`, of the others the expert layer;
* gated attention: `q = a W_q` (heads x d), `k, v = a W_k, a W_v`
  (kv_heads x d), causal `softmax(q k^T / sqrt(d)) v` with kv head j
  serving query heads `j * group ...` (`ops.flash_attention` takes the
  fewer kv heads as they are); `out = W_o [attn * sigmoid(a W_gate)]`,
  the gate elementwise, a column a head and channel
  (arXiv:2505.06708);
* latent attention without positions and without a query compression
  (`attention_kind` "latent"): `q = a W_q` (heads x (nope + rope));
  `[c_kv | k_s] = a W_kva`, `c_kv = RMSNorm(c_kv)`, `[k_n | v] = c_kv
  W_kvb` a head; head h's key is `[k_n,h | k_s]`, the `rope`-wide row
  `k_s` shared by the heads and, like q, never rotated; causal
  `softmax(q k^T / sqrt(nope + rope)) v`, `out = W_o ctx`.  The
  key-value side, the flash call and the projection are
  `HeldExpertsLM`'s, which `models.mla_moe` rotates around;
* KDA, n heads of d_k = d_v = d: `q, k, v = SiLU(conv(a W_.))`, a
  causal depthwise convolution over time, `conv_kernel` taps, a weight
  a channel and tap; a head's q and k scaled to unit length, q by
  d^-1/2 more; the log-decay a channel `g = -exp(A_h) softplus(W_f2
  (W_f1 a) + dt_bias)` in float32; `beta = sigmoid(W_beta a)`, twice
  that where `allow_neg_eigval`; the state and the outputs by
  `ops.delta_rule.gated_delta_rule`; `out = W_o [RMSNorm_d(o) *
  sigmoid(W_g2 (W_g1 a))]`, the norm's weight of d shared by the heads;
* the expert layer: `moe.HeldExpertsMLP`, which routes over all
  `n_routed_experts` by the bias-corrected sigmoid gate and computes
  the part of the result that experts `[experts_first, experts_first +
  experts_count)` give, plus the shared expert.

**Documents.**  With `eod_token_id` a row is documents packed end to
end, each closed by that id: a token's document is the number of EODs
before it, and a token starts one where it is the row's first or
follows an EOD.  Nothing a mixer computes for a token reads a token of
another document: the flash call takes the documents as segment ids, a
convolution tap that would reach into the document before reads 0, and
the delta rule's state is zero before a document's first token
(`gated_delta_rule(resets=)`).  The model derives all of it from its
`tokens`, once a step, under the scope `block0/attn/segments`; norms,
FFNs, the router, the head and the loss are a token at a time and know
nothing of it.  None, the default: a row is one document.

The config says what is held here: how many layers and which of them
attend, which experts, how many rows of the vocabulary.  The layers
left out lie on other chips as pipeline stages, the experts left out
on the other chips of the expert-parallel group; this module has no
code that stands in for either.

Runs shard-local inside `shard_map` over the (pp, dp, tp) mesh with the
surface `models.mla_moe.MLAMoE` gives the step builder (`init`,
`partition_specs`, `trunk`, `token_losses`, `loss`, `routing_counts`;
the two share `models.held_experts_lm.HeldExpertsLM`), tensor
parallelism 1 only.  Activations are (B, S, H); the two kernels' side
is head-major, (B, heads, S, d), and the copies between the two carry
the scope of what they feed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.models.held_experts_lm import HeldExpertsLM
from apex_tpu.ops.conv_stage import stage_conv_heads
from apex_tpu.ops.delta_rule import gated_delta_rule
from apex_tpu.ops.rope_stage import stage_heads
from apex_tpu.parallel.mesh import TP_AXIS


@dataclasses.dataclass(frozen=True)
class HybridMoEConfig:
    vocab_size: int = 24576          # rows of embedding and head held here
    hidden: int = 4096
    num_layers: int = 4              # layers held here
    attention_layers: Tuple[int, ...] = (0,)   # which of them attend
    attention_kind: str = "gqa"      # "gqa" (gated, grouped) or "latent"
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    # the latent kind's widths (it reads none of the two above)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64       # unrotated: the width is all it keeps
    v_head_dim: int = 128
    kda_heads: int = 64
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kda_rank: int = 128              # inner width of the f and g pairs
    allow_neg_eigval: bool = True
    first_k_dense_replace: int = 0   # leading layers with a dense SwiGLU
    intermediate_size: int = 0       # its width
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320      # the router's width, as published
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    experts_first: int = 0           # experts [first, first + count)
    experts_count: int = 8
    # rows of the grouped GEMMs' buffer over what uniform routing sends
    # the held experts (`HeldExpertsMLP.rows_bound`)
    expert_rows_factor: float = 2.0
    init_std: float = 0.02
    router_bias_range: float = 0.05  # the seeded, fixed selection bias
    dtype: Any = jnp.float32
    logits_dtype: Any = None         # None keeps fp32 logits
    # True puts a `jax.checkpoint` around every mixer.  Of a KDA mixer a
    # step keeps its normed input and the activations `KEPT` names, and
    # recomputes the rest in the backward; of a layer that attends it
    # keeps everything and recomputes nothing
    recompute_mixers: bool = False
    scan_chunk: Optional[int] = None   # None: the tuner's, or the op's
    # the id that closes a document of a packed row; None: a row is one
    eod_token_id: Optional[int] = None
    # the dispatch of the flash kernels, as `flash_attention` takes it
    flash_override: Any = None
    fused_xent: Any = None
    axis_name: str = TP_AXIS


# What a checkpointed KDA mixer keeps besides its normed input, by the
# `checkpoint_name` of each: the q, k, v projections' outputs in front
# of the convolution (its backward reads them) and behind it, staged
# for the scan (the scan's backward reads those); the rank-`kda_rank`
# inner activations of the decay and gate pairs and beta's logits; o as
# the scan returns it; the normed, gated o the output projection reads.
# A (B, S, n * d) array each but the small three: dear to recompute (a
# GEMM over H, a pass of the convolution, the scan's outputs, the norm's
# turn) and small to hold.  Not kept: the float32 log-decay and
# whatever the scan's own backward rebuilds from its inputs (the
# chunk-local stage, the chunk-start states)
KEPT = ("kda_q", "kda_k", "kda_v", "kda_staged", "kda_f", "kda_g",
        "kda_beta", "kda_o", "kda_gated")

_BY_NAME = jax.checkpoint_policies.save_only_these_names(*KEPT)
_kept = {"kept_bytes": 0}


def _keeps(prim, *avals, **params):
    """A checkpointed KDA mixer's policy: `save_only_these_names(*KEPT)`,
    which counts what it saves.  JAX asks it once an equation whose
    inputs the forward knows, when the mixer is differentiated.  One
    function for every layer, and not for the count's sake alone: with
    a policy made anew a layer the TPU compiler wrote the decay's
    recomputed GEMM in float32 and relaid it, 4.8 ms and 200 MB a step
    in cell 5 (PERF.md section 6, PR 38)."""
    saved = _BY_NAME(prim, *avals, **params)
    if saved:
        _kept["kept_bytes"] += sum(a.size * a.dtype.itemsize for a in avals)
    return saved


def stats():
    """{"kept_bytes": the bytes the KDA mixers' checkpoints were told to
    keep for the backward besides their inputs, shape x itemsize of every
    activation the policy answered yes for} of the stacks differentiated
    since the last `reset_stats()`.  0 where no policy was asked: the
    flag false, a checkpoint without one, a name `KEPT` lacks."""
    return dict(_kept)


def reset_stats():
    _kept["kept_bytes"] = 0


class HybridMoE(HeldExpertsLM):
    def __init__(self, config: HybridMoEConfig):
        if config.attention_kind not in ("gqa", "latent"):
            raise ValueError(f"attention_kind {config.attention_kind!r} "
                             "is neither 'gqa' nor 'latent'")
        super().__init__(config)

    def _attends(self, i: int) -> bool:
        return i in self.c.attention_layers

    def _is_dense(self, i: int) -> bool:
        return i < self.c.first_k_dense_replace

    # ------------------------------ params --------------------------------
    def _init_block(self, key, i: int) -> dict:
        c = self.c
        ks = jax.random.split(key, 16)
        h = c.hidden

        def normal(k, *shape):
            return jax.random.normal(k, shape, c.dtype) * c.init_std

        def ones(n):
            return {"weight": jnp.ones((n,), c.dtype)}

        if self._attends(i) and c.attention_kind == "latent":
            nh, dn, dv = c.num_heads, c.qk_nope_head_dim, c.v_head_dim
            attn = {
                "q": normal(ks[0], h, nh * (dn + c.qk_rope_head_dim)),
                "kv_a": normal(ks[1], h, c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_a_norm": ones(c.kv_lora_rank),
                "kv_b": normal(ks[2], c.kv_lora_rank, nh * (dn + dv)),
                "proj": normal(ks[4], nh * dv, h)}
        elif self._attends(i):
            wide, kv = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
            attn = {"q": normal(ks[0], h, wide), "k": normal(ks[1], h, kv),
                    "v": normal(ks[2], h, kv), "gate": normal(ks[3], h, wide),
                    "proj": normal(ks[4], wide, h)}
        else:
            n, d, r = c.kda_heads, c.kda_head_dim, c.kda_rank
            wide = n * d
            # A_h and dt_bias as the paper's code starts them: A uniform
            # in (1, 16), a time step log-uniform in (1e-3, 1e-1) and the
            # bias its inverse softplus
            dt = jnp.exp(jax.random.uniform(
                ks[12], (wide,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            attn = {
                "q": normal(ks[0], h, wide), "k": normal(ks[1], h, wide),
                "v": normal(ks[2], h, wide),
                # a tap's weight a channel, uniform in +-1/sqrt(taps) as
                # a depthwise convolution is started
                **{f"conv_{x}": jax.random.uniform(
                    k, (c.conv_kernel, wide), c.dtype, -1.0, 1.0)
                   / math.sqrt(c.conv_kernel)
                   for x, k in zip("qkv", ks[5:8])},
                "f_a": normal(ks[8], h, r), "f_b": normal(ks[9], r, wide),
                "a_log": jnp.log(jax.random.uniform(
                    ks[11], (n,), jnp.float32, 1.0, 16.0)).astype(c.dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(c.dtype),
                "beta": normal(ks[13], h, n),
                "g_a": normal(ks[14], h, r), "g_b": normal(ks[15], r, wide),
                "o_norm": ones(d),
                "proj": normal(ks[4], wide, h),
            }
        if self._is_dense(i):
            k_up, k_down = jax.random.split(ks[10])
            mlp = {"gate_up": normal(k_up, h, 2 * c.intermediate_size),
                   "down": normal(k_down, c.intermediate_size, h)}
        else:
            mlp = self.experts.init(ks[10], c.dtype)
        return {"ln1": ones(h), "attn": attn, "ln2": ones(h), "mlp": mlp}

    # ------------------------------ forward -------------------------------
    def _attention(self, p, a, docs=None):
        """a: (B, S, H), normed.  The gated grouped-query attention's
        output, before the residual add."""
        c = self.c
        q, k, v = self._qkv(p, a)
        with jax.named_scope("flash"):
            ctx = self._attend(
                self._heads(q, c.num_heads), self._heads(k, c.num_kv_heads),
                self._heads(v, c.num_kv_heads),
                None if docs is None else docs.ids)
        with jax.named_scope("gate"):
            ctx = ctx * jax.nn.sigmoid(self._dot(a, p["gate"]))
        with jax.named_scope("proj"):
            return self._dot(ctx, p["proj"])

    def _latent(self, p, a, docs=None):
        """a: (B, S, H), normed.  The latent attention's output, before
        the residual add: nothing is rotated and q is not compressed.
        `q` runs as two GEMMs over its column groups, as `MLAMoE`'s
        `q_b` does, so that `stage_heads` reads each where it lies and
        writes q and k head-major in one pass each, k's shared row once
        a head."""
        c = self.c
        nh, dn, dr = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
        with jax.named_scope("q"):
            w = p["q"].reshape(-1, nh, dn + dr)
            q_n = self._dot(a, w[..., :dn].reshape(-1, nh * dn))
            q_s = self._dot(a, w[..., dn:].reshape(-1, nh * dr))
        k_n, k_s, v = self._latent_kv(p, a, rotary=False)
        with jax.named_scope("stage"):
            q = stage_heads(q_n, q_s, nh, per_head=True,
                            use_pallas_override=c.flash_override)
            k = stage_heads(k_n, k_s, nh,
                            use_pallas_override=c.flash_override)
        return self._latent_attend(
            p, q, k, v, segment_ids=None if docs is None else docs.ids)

    def scan_inputs(self, p, a, docs=None):
        """a: (B, S, H), normed.  What Kimi Delta Attention hands
        `gated_delta_rule`, head-major: q, k, v (B, n, S, d) in the
        model's dtype, the log-decay g (B, n, S, d) and beta (B, n, S)
        in float32.  `docs`: the row's `documents`, for the taps."""
        c = self.c
        b, s, _ = a.shape
        n, d = c.kda_heads, c.kda_head_dim
        f32 = jnp.float32
        with jax.named_scope("qkv"):
            q, k, v = (self._keep(self._dot(a, p[x]), f"kda_{x}")
                       for x in "qkv")
        with jax.named_scope("conv"):
            # q's and k's heads of unit length, q by d^-1/2 more
            q, k, v = (self._keep(x, "kda_staged") for x in stage_conv_heads(
                (q, k, v), [p[f"conv_{x}"] for x in "qkv"], n,
                (d ** -0.5, 1.0, None),
                ids=None if docs is None else docs.ids,
                masks=None if docs is None else docs.taps,
                use_pallas_override=c.flash_override))
        with jax.named_scope("decay"):
            f = self._dot(self._keep(self._dot(a, p["f_a"]), "kda_f"),
                          p["f_b"])
            rate = jnp.exp(p["a_log"].astype(f32))[:, None]
            g = -rate * jax.nn.softplus(
                f.astype(f32) + p["dt_bias"].astype(f32)).reshape(b, s, n, d)
            g = g.transpose(0, 2, 1, 3)
            beta = jax.nn.sigmoid(self._keep(jnp.dot(
                a, p["beta"], preferred_element_type=f32), "kda_beta"))
            if c.allow_neg_eigval:
                beta = 2.0 * beta
            beta = beta.transpose(0, 2, 1)
        return q, k, v, g, beta

    def _kda(self, p, a, docs=None):
        """a: (B, S, H), normed.  Kimi Delta Attention's output, before
        the residual add."""
        c = self.c
        b, s, _ = a.shape
        n, d = c.kda_heads, c.kda_head_dim
        f32 = jnp.float32
        q, k, v, g, beta = self.scan_inputs(p, a, docs)
        with jax.named_scope("scan"):
            o = self._keep(gated_delta_rule(
                q, k, v, g, beta, chunk=c.scan_chunk,
                resets=None if docs is None else docs.first), "kda_o")
        with jax.named_scope("onorm"):
            o = o.transpose(0, 2, 1, 3).astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + c.rms_norm_eps)
            o = (o * p["o_norm"]["weight"].astype(f32)).reshape(b, s, n * d)
            gate = self._dot(self._keep(self._dot(a, p["g_a"]), "kda_g"),
                             p["g_b"])
            o = self._keep(
                (o * jax.nn.sigmoid(gate.astype(f32))).astype(c.dtype),
                "kda_gated")
        with jax.named_scope("proj"):
            return self._dot(o, p["proj"])

    def _block(self, i, p, x, docs=None):
        """`docs`: the row's `documents`; an input of the checkpointed
        mixer under `recompute_mixers`, not recomputed work.  Under the
        flag a KDA mixer keeps its normed input and what `KEPT` names
        and runs again, in the backward, the decay, the scan up to its
        chunk-start states and the norm's elementwise part; a layer that
        attends keeps everything and runs nothing twice.  Its checkpoint
        is one in form alone, and is there for the set-up's sake: with
        the layer under none the one binding of `flash_bwd` traced for
        4.9 s and not 0.4 on the chip's host, cell 5's warm `setup_s`
        56-59 s and not 51-53, for 0.4% more tokens/s and 0.1 GB less
        (PERF.md section 6, PR 38's run of both forms)."""
        attends = self._attends(i)
        if not attends:
            mixer = self._kda
        elif self.c.attention_kind == "latent":
            mixer = self._latent
        else:
            mixer = self._attention
        if self.c.recompute_mixers:
            mixer = jax.checkpoint(
                mixer, policy=jax.checkpoint_policies.everything_saveable
                if attends else _keeps)
        with jax.named_scope(f"block{i}"):
            with jax.named_scope("ln1"):
                a = self._norm(p["ln1"], x)
            with jax.named_scope("attn"):
                x = x + mixer(p["attn"], a, docs)
            with jax.named_scope("ln2"):
                m = self._norm(p["ln2"], x)
            with jax.named_scope("mlp"):
                if self._is_dense(i):
                    return x + self._swiglu(p["mlp"], m), None
                y, stats = self.experts.apply(p["mlp"], m)
                return x + y, stats

    def trunk(self, params, tokens):
        """tokens (B, S) -> (the residual stream after the last held
        layer, (B, S, H), before the final norm; the expert layers'
        HeldExpertsStats in layer order)."""
        h = self._embed(params, tokens)
        docs = self.documents(tokens)
        stats = []
        for i in range(self.c.num_layers):
            h, st = self._block(i, params[f"block{i}"], h, docs)
            if st is not None:
                stats.append(st)
        return h, stats
