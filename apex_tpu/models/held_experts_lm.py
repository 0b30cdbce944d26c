"""What the decoders built on `moe.HeldExpertsMLP` share
(`models.mla_moe.MLAMoE`, `models.hybrid_moe.HybridMoE`,
`models.shortconv_moe.ShortConvMoE`): the two ends of the network — a
vocabulary-parallel embedding, a final RMSNorm, a head over the held
rows of the vocabulary (a leaf of its own, or, where the config has
`tie_word_embeddings`, the embedding's leaf read a second time) and
the fused cross entropy — the expert layer one chip of an
expert-parallel group holds, built from the config's keys (none where
the config routes to no expert: `models.olmo_hybrid.OlmoHybrid`, whose
every FFN is the dense SwiGLU), the documents of a packed row, the small
pieces every block uses, the dense SwiGLU of a leading layer, the part
of grouped-query attention that does not depend on what a model does
to q and k between the projections and the kernels (the three
projections, the heads' reshape, the flash call with fewer kv heads:
`HybridMoE` attends on them as they are and gates the context,
`ShortConvMoE` norms and rotates q and k first), and the part of latent
attention that does not depend on how a model makes its queries or
whether it turns anything: the compressed key-value projection with its
norm, its expansion as two GEMMs, the flash call and the output
projection (`MLAMoE` compresses q and rotates; `HybridMoE`'s latent
layers do neither); and, for a stack with one head, the surface the
step builder takes (`init`, `apply`, `token_losses`, `loss`,
`routing_counts`) over the subclass's `_init_block` and `trunk`.

A config gives: vocab_size, hidden, init_std, rms_norm_eps, dtype,
logits_dtype, fused_xent, axis_name, and, where it routes (a config
without n_routed_experts routes nowhere), of the expert layer
moe_intermediate_size, n_routed_experts, experts_first, experts_count,
num_experts_per_tok, n_shared_experts, routed_scaling_factor,
norm_topk_prob, router_bias_range and, where it has one,
expert_rows_factor (`HeldExpertsMLP`'s `rows_factor`) or
tie_word_embeddings; where it has grouped-query attention, num_heads,
num_kv_heads, head_dim and flash_override; where it has latent
attention, num_heads, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim and flash_override; where its rows are
packed documents, eod_token_id and conv_kernel.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from apex_tpu.moe.layer import HeldExpertsMLP
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.layer_norm import fused_rms_norm
from apex_tpu.ops.rope_stage import halves
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    VocabParallelEmbedding,
)


class Documents(NamedTuple):
    """What the mixers know of a packed row: `ids` (B, S) int32, a
    token's document; `first` (B, S) bool, the tokens that start one;
    `taps[r - 1]` (B, S, 1) float32, 1 where the token r back is of the
    same document and 0 where it is not, or lies before the row."""
    ids: jnp.ndarray
    first: jnp.ndarray
    taps: Tuple[jnp.ndarray, ...]


class HeldExpertsLM:
    def __init__(self, config):
        self.c = c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden, init_std=c.init_std,
            axis_name=c.axis_name)
        self.experts = None         # a config that routes to no expert
        if getattr(c, "n_routed_experts", 0):
            self.experts = HeldExpertsMLP(
                c.hidden, c.moe_intermediate_size, c.n_routed_experts,
                first=c.experts_first, count=c.experts_count,
                top_k=c.num_experts_per_tok, n_shared=c.n_shared_experts,
                scale=c.routed_scaling_factor,
                renormalize=c.norm_topk_prob, init_std=c.init_std,
                bias_range=c.router_bias_range,
                rows_factor=getattr(c, "expert_rows_factor", 2.0))

    # ------------------------------ params --------------------------------
    @property
    def tied(self) -> bool:
        """The head reads the embedding's leaf: one (V, H) array, the
        gradients of both uses summed, the optimizer over it once."""
        return bool(getattr(self.c, "tie_word_embeddings", False))

    def _init_ends(self, k_embed, k_head) -> dict:
        """embed, final_ln and, where it is a leaf of its own, head."""
        c = self.c
        ends = {"embed": self.embed.init(k_embed, c.dtype),
                "final_ln": {"weight": jnp.ones((c.hidden,), c.dtype)}}
        if not self.tied:
            ends["head"] = {"weight": jax.random.normal(
                k_head, (c.vocab_size, c.hidden), c.dtype) * c.init_std}
        return ends

    def init(self, key):
        """The ends and `num_layers` blocks of the subclass's
        `_init_block(key, i)`."""
        keys = jax.random.split(key, 2 + self.c.num_layers)
        params = self._init_ends(keys[0], keys[1])
        for i in range(self.c.num_layers):
            params[f"block{i}"] = self._init_block(keys[2 + i], i)
        return params

    def partition_specs(self):
        """PartitionSpec pytree matching init(): the vocabulary's rows
        over the tp axis (of size 1), everything else replicated."""
        c = self.c
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        specs = jax.tree.map(lambda _: P(), shapes)
        specs["embed"] = {"weight": P(c.axis_name, None)}
        if not self.tied:
            specs["head"] = {"weight": P(c.axis_name, None)}
        return specs

    # ------------------------------ forward -------------------------------
    def documents(self, tokens, i: int = 0) -> Optional[Documents]:
        """The documents of `tokens` (B, S), for every mixer of the
        step; None where the config names no `eod_token_id`.  Whoever
        runs the blocks derives them once; the time is filed under
        block i, the first it runs."""
        c = self.c
        if c.eod_token_id is None:
            return None
        with jax.named_scope(f"block{i}"), jax.named_scope("attn"), \
                jax.named_scope("segments"):
            # the EOD belongs to the document it closes
            after_eod = jnp.pad(tokens[:, :-1] == c.eod_token_id,
                                ((0, 0), (1, 0)))
            ids = jnp.cumsum(after_eod, axis=1, dtype=jnp.int32)
            back = lambda r: jnp.pad(ids[:, :-r], ((0, 0), (r, 0)),
                                     constant_values=-1)
            return Documents(
                ids=ids, first=back(1) != ids,
                taps=tuple((back(r) == ids)[..., None].astype(jnp.float32)
                           for r in range(1, c.conv_kernel)))

    def _keep(self, x, name):
        """`x` under the name `name`, for the checkpoint's policy of a
        stack that has `recompute_mixers` to know it by; `x` itself
        with the flag false."""
        return checkpoint_name(x, name) if self.c.recompute_mixers else x

    def _norm(self, p, x):
        return fused_rms_norm(x, p["weight"], eps=self.c.rms_norm_eps)

    def _dot(self, x, w):
        return jnp.dot(x, w, preferred_element_type=jnp.float32
                       ).astype(x.dtype)

    def _swiglu(self, p, m):
        """A dense layer's FFN: `down(silu(gate) * up)` of p's packed
        `gate_up` (H, 2f) and `down` (f, H)."""
        with jax.named_scope("gate_up"):
            gate, up = jnp.split(self._dot(m, p["gate_up"]), 2, axis=-1)
            act = jax.nn.silu(gate) * up
        with jax.named_scope("down"):
            return self._dot(act, p["down"])

    @staticmethod
    def _heads(x, n):
        """(B, S, n * d) -> (B, n, S, d)."""
        b, s, w = x.shape
        return x.reshape(b, s, n, w // n).transpose(0, 2, 1, 3)

    def _qkv(self, p, a):
        """a: (B, S, H), normed.  Grouped-query attention's three
        projections, token-major: q (B, S, heads * d), k and v (B, S,
        kv_heads * d)."""
        with jax.named_scope("qkv"):
            return tuple(self._dot(a, p[x]) for x in "qkv")

    def _attend(self, q, k, v, segment_ids=None):
        """Causal attention of head-major q (B, heads, S, d) over k, v
        (B, kv_heads, S, d), kv head j serving query heads `j * group
        ...` (`ops.flash_attention` takes the fewer kv heads as they
        are), within a row's documents where `segment_ids` (B, S) names
        them: the context, token-major again, (B, S, heads * d)."""
        c = self.c
        b, _, s, _ = q.shape
        ctx = flash_attention(
            q, k, v, causal=True, softmax_scale=1.0 / math.sqrt(c.head_dim),
            segment_ids=segment_ids, use_pallas_override=c.flash_override)
        return ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)

    def _latent_kv(self, p, a, rotary: bool):
        """a: (B, S, H), normed.  Latent attention's key-value side,
        token-major: (k_n (B, S, heads * nope), the heads' shared row
        k_s (B, S, rope), v (B, S, heads * v)).  `kv_b` runs as two
        GEMMs over its column groups, so that each output lies as its
        one reader takes it.  With `rotary` the shared row's columns
        are taken in halves order (`ops.rope_stage.halves`), as the
        rotation that follows wants them."""
        c = self.c
        nh, dn, dv = c.num_heads, c.qk_nope_head_dim, c.v_head_dim
        with jax.named_scope("kv_a"):
            w = halves(p["kv_a"], c.kv_lora_rank) if rotary else p["kv_a"]
            ckv = self._dot(a, w)
            k_s = ckv[..., c.kv_lora_rank:]
            c_kv = self._norm(p["kv_a_norm"], ckv[..., :c.kv_lora_rank])
        with jax.named_scope("kv_b"):
            w = p["kv_b"].reshape(-1, nh, dn + dv)
            k_n = self._dot(c_kv, w[..., :dn].reshape(-1, nh * dn))
            v = self._dot(c_kv, w[..., dn:].reshape(-1, nh * dv))
        return k_n, k_s, v

    def _latent_attend(self, p, q, k, v, segment_ids=None):
        """Causal attention of head-major q, k (B, heads, S, nope +
        rope) over the token-major v of `_latent_kv`, within a row's
        documents where `segment_ids` (B, S) names them, and the output
        projection.  The head-major copies of v and of the context are
        the kernels' price, as in the GPT block: they carry the flash
        scope."""
        c = self.c
        b, _, s, width = q.shape
        nh, dv = c.num_heads, c.v_head_dim
        with jax.named_scope("flash"):
            ctx = flash_attention(
                q, k, v.reshape(b, s, nh, dv).transpose(0, 2, 1, 3),
                causal=True, softmax_scale=1.0 / math.sqrt(width),
                segment_ids=segment_ids,
                use_pallas_override=c.flash_override)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * dv)
        with jax.named_scope("proj"):
            return self._dot(ctx, p["proj"])

    def _embed(self, params, ids):
        with jax.named_scope("embed"):
            return self.embed.apply(params["embed"], ids)

    def _final_ln(self, params, h):
        with jax.named_scope("final_ln"):
            return self._norm(params["final_ln"], h)

    def logits_local(self, params, h):
        """The head over the held rows: (B, S, V/tp)."""
        with jax.named_scope("head"):
            return self._head(params, h)

    def _head(self, params, h):
        out_dtype = self.c.logits_dtype or jnp.float32
        weight = params["embed" if self.tied else "head"]["weight"]
        return jnp.einsum("bsh,vh->bsv", h, weight,
                          preferred_element_type=jnp.float32
                          ).astype(out_dtype)

    def _xent(self, logits, labels):
        return vocab_parallel_cross_entropy(
            logits, labels, axis_name=self.c.axis_name,
            fused=self.c.fused_xent)

    # What follows is a stack's surface for the step builder, over the
    # subclass's `trunk(params, tokens)` -> (the residual stream after
    # the last held layer, the expert layers' HeldExpertsStats in layer
    # order); `MLAMoE`, which has a second head, brings its own.
    def apply(self, params, tokens, key=None):
        """tokens: (B, S) ids within the held rows.  The hidden states
        the head reads, (B, S, H).  Shard-local: call inside
        shard_map."""
        h, _ = self.trunk(params, tokens)
        return self._final_ln(params, h)

    def token_losses(self, params, tokens, labels):
        """(main, None, stats): per-token cross entropies (B, S) fp32
        against `labels`, no second head (`MLAMoE.token_losses` has
        one), and every expert layer's HeldExpertsStats."""
        h, stats = self.trunk(params, tokens)
        logits = self.logits_local(params, self._final_ln(params, h))
        with jax.named_scope("loss"):
            return self._xent(logits, labels), None, stats

    def loss(self, params, tokens, labels, key=None):
        """The mean over tokens.  tokens/labels: (B, S)."""
        main, _, _ = self.token_losses(params, tokens, labels)
        with jax.named_scope("loss"):
            return jnp.mean(main)

    def routing_counts(self, params, tokens, labels=None):
        """Forward only, without the head: (counts (layers,
        experts_count) int32, overflow (layers,) int32) of every expert
        layer held; (0, 0) and (0,) where none is."""
        return self._counts(self.trunk(params, tokens)[1])

    @staticmethod
    def _counts(stats):
        """(counts (layers, experts_count) int32, overflow (layers,)
        int32) of the expert layers' HeldExpertsStats in layer order."""
        if not stats:
            return (jnp.zeros((0, 0), jnp.int32), jnp.zeros((0,), jnp.int32))
        return (jnp.stack([s.counts for s in stats]),
                jnp.stack([s.overflow for s in stats]))
