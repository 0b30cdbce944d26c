"""ShortConvMoE — a decoder whose token mixers are gated short
convolutions, with grouped-query softmax attention on the layers
`layer_types` names, over expert layers without a shared expert, as one
chip of an expert-parallel group holds it (LFM2's `lfm2_moe` stack).

The layers (no bias anywhere, RMSNorm, `a` a block's normed input):

* block: `h = x + Mixer_i(RMSNorm(x))`, `y = h + FFN_i(RMSNorm(h))`,
  the FFN of the leading `num_dense_layers` layers a SwiGLU of
  `intermediate_size`, of the others the expert layer; a final RMSNorm
  before the head;
* gated short convolution (`"conv"`): `[B | C | u] = a W_in` (H -> 3H);
  `z = B * u`; `c_t = sum_j w[:, j] z_(t - taps + 1 + j)`, a causal
  depthwise convolution with one weight a channel and tap and zeros
  before the row (`ops.short_conv.gated_short_conv`); `out = (C * c)
  W_out`.  No activation, no state but the `taps - 1` previous `z`;
* attention (`"attention"`): `q = a W_q` (heads x d), `k, v = a W_k, a
  W_v` (kv_heads x d); q and k each through an RMSNorm over a head's d
  channels (one weight of d for q, one for k, shared by the heads);
  rotary embedding over all d channels in halves order (`x1 c - x2 s |
  x2 c + x1 s`, the partners d / 2 apart) at `rope_theta`; causal
  `softmax(q k^T / sqrt(d)) v` with kv head j serving query heads `j *
  group ...`; `out = ctx W_o`; no gate.  The projections, the heads'
  reshape and the flash call are `HeldExpertsLM`'s, which
  `models.hybrid_moe` attends through without the norm and the turn;
* the expert layer: `moe.HeldExpertsMLP` with no shared expert, which
  routes over all `n_routed_experts` by the bias-corrected sigmoid gate
  and computes the part of the result that experts `[experts_first,
  experts_first + experts_count)` give;
* the head: over the held rows of the vocabulary, the embedding's own
  leaf where `tie_word_embeddings` (`HeldExpertsLM`).

The config says what is held here: how many layers and of which kind,
which experts, how many rows of the vocabulary.  The layers left out
lie on other chips as pipeline stages, the experts left out on the
other chips of the expert-parallel group; this module has no code that
stands in for either.

Runs shard-local inside `shard_map` over the (pp, dp, tp) mesh with the
surface `models.mla_moe.MLAMoE` gives the step builder (`init`,
`partition_specs`, `trunk`, `token_losses`, `loss`, `routing_counts`),
tensor parallelism 1 only.  Activations are (B, S, H); the flash
kernels' side is head-major, (B, heads, S, d), and the copies between
the two carry the scope of what they feed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.models.held_experts_lm import HeldExpertsLM
from apex_tpu.ops.rope_stage import rope_tables, turn_halves
from apex_tpu.ops.short_conv import gated_short_conv
from apex_tpu.parallel.mesh import TP_AXIS

KINDS = ("conv", "attention")


@dataclasses.dataclass(frozen=True)
class ShortConvMoEConfig:
    vocab_size: int = 32768          # rows of the embedding held here
    hidden: int = 2048
    num_layers: int = 6              # layers held here
    # the mixer of each held layer: "conv" or "attention"
    layer_types: Tuple[str, ...] = ("conv", "conv", "attention", "conv",
                                    "conv", "conv")
    conv_kernel: int = 3             # taps of the short convolution
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    num_dense_layers: int = 2        # leading layers with a dense SwiGLU
    intermediate_size: int = 7168    # its width
    moe_intermediate_size: int = 1792
    n_routed_experts: int = 32       # the router's width, as published
    num_experts_per_tok: int = 4
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    experts_first: int = 0           # experts [first, first + count)
    experts_count: int = 16
    tie_word_embeddings: bool = True   # the head reads the embedding
    init_std: float = 0.02
    router_bias_range: float = 0.05  # the seeded, fixed selection bias
    dtype: Any = jnp.float32
    logits_dtype: Any = None         # None keeps fp32 logits
    # the dispatch of the flash kernels, as `flash_attention` takes it
    flash_override: Any = None
    fused_xent: Any = None
    axis_name: str = TP_AXIS


class ShortConvMoE(HeldExpertsLM):
    def __init__(self, config: ShortConvMoEConfig):
        c = config
        if len(c.layer_types) != c.num_layers or not set(
                c.layer_types) <= set(KINDS):
            raise ValueError(
                f"layer_types {c.layer_types!r} does not name one of "
                f"{KINDS} for each of the {c.num_layers} layers")
        if c.head_dim % 2:
            raise ValueError(f"head_dim {c.head_dim} has no halves to turn")
        super().__init__(config)

    def _attends(self, i: int) -> bool:
        return self.c.layer_types[i] == "attention"

    def _is_dense(self, i: int) -> bool:
        return i < self.c.num_dense_layers

    # ------------------------------ params --------------------------------
    def _init_block(self, key, i: int) -> dict:
        c = self.c
        ks = jax.random.split(key, 6)
        h = c.hidden

        def normal(k, *shape):
            return jax.random.normal(k, shape, c.dtype) * c.init_std

        def ones(n):
            return {"weight": jnp.ones((n,), c.dtype)}

        if self._attends(i):
            wide, kv = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
            attn = {"q": normal(ks[0], h, wide), "k": normal(ks[1], h, kv),
                    "v": normal(ks[2], h, kv), "q_norm": ones(c.head_dim),
                    "k_norm": ones(c.head_dim), "proj": normal(ks[3], wide, h)}
        else:
            attn = {
                "in_proj": normal(ks[0], h, 3 * h),
                # a tap's weight a channel, uniform in +-1/sqrt(taps) as
                # a depthwise convolution is started
                "conv": jax.random.uniform(
                    ks[1], (h, c.conv_kernel), c.dtype, -1.0, 1.0)
                / math.sqrt(c.conv_kernel),
                "out_proj": normal(ks[3], h, h)}
        if self._is_dense(i):
            mlp = {"gate_up": normal(ks[4], h, 2 * c.intermediate_size),
                   "down": normal(ks[5], c.intermediate_size, h)}
        else:
            mlp = self.experts.init(ks[4], c.dtype)
        return {"ln1": ones(h), "attn": attn, "ln2": ones(h), "mlp": mlp}

    # ------------------------------ forward -------------------------------
    def _shortconv(self, p, a):
        """a: (B, S, H), normed.  The gated short convolution's output,
        before the residual add."""
        with jax.named_scope("in_proj"):
            bcu = self._dot(a, p["in_proj"])
        with jax.named_scope("shortconv"):
            gated = gated_short_conv(bcu, p["conv"])
        with jax.named_scope("out_proj"):
            return self._dot(gated, p["out_proj"])

    def normed_turned(self, x, weight, n):
        """x (B, S, n * d), a projection's output -> (B, n, S, d), the
        flash kernels' operand: each head's d channels through an
        RMSNorm whose `weight` (d,) the heads share, then turned by the
        token's position, in float32, and written head-major."""
        c = self.c
        b, s, _ = x.shape
        f32 = jnp.float32
        x = x.reshape(b, s, n, c.head_dim).astype(f32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + c.rms_norm_eps) * weight.astype(f32)
        cos, sin = rope_tables(s, c.head_dim, c.rope_theta)
        return turn_halves(x, cos[:, None], sin[:, None]).astype(
            c.dtype).transpose(0, 2, 1, 3)

    def _attention(self, p, a):
        """a: (B, S, H), normed.  The QK-normed rotary grouped-query
        attention's output, before the residual add."""
        c = self.c
        q, k, v = self._qkv(p, a)
        with jax.named_scope("qknorm_rope"):
            q = self.normed_turned(q, p["q_norm"]["weight"], c.num_heads)
            k = self.normed_turned(k, p["k_norm"]["weight"], c.num_kv_heads)
        with jax.named_scope("flash"):
            ctx = self._attend(q, k, self._heads(v, c.num_kv_heads))
        with jax.named_scope("proj"):
            return self._dot(ctx, p["proj"])

    def _block(self, i, p, x):
        mixer = self._attention if self._attends(i) else self._shortconv
        with jax.named_scope(f"block{i}"):
            with jax.named_scope("ln1"):
                a = self._norm(p["ln1"], x)
            with jax.named_scope("attn"):
                x = x + mixer(p["attn"], a)
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
        stats = []
        for i in range(self.c.num_layers):
            h, st = self._block(i, params[f"block{i}"], h)
            if st is not None:
                stats.append(st)
        return h, stats
