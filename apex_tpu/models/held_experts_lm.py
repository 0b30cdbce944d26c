"""What the decoders built on `moe.HeldExpertsMLP` share
(`models.mla_moe.MLAMoE`, `models.hybrid_moe.HybridMoE`): the two ends
of the network — a vocabulary-parallel embedding, a final RMSNorm, an
untied head over the held rows of the vocabulary and the fused cross
entropy — the expert layer one chip of an expert-parallel group holds,
built from the config's keys, and the small pieces every block uses.

A config gives: vocab_size, hidden, init_std, rms_norm_eps, dtype,
logits_dtype, fused_xent, axis_name, and of the expert layer
moe_intermediate_size, n_routed_experts, experts_first, experts_count,
num_experts_per_tok, n_shared_experts, routed_scaling_factor,
norm_topk_prob, router_bias_range and, where it has one,
expert_rows_factor (`HeldExpertsMLP`'s `rows_factor`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.moe.layer import HeldExpertsMLP
from apex_tpu.ops.layer_norm import fused_rms_norm
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    VocabParallelEmbedding,
)


class HeldExpertsLM:
    def __init__(self, config):
        self.c = c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden, init_std=c.init_std,
            axis_name=c.axis_name)
        self.experts = HeldExpertsMLP(
            c.hidden, c.moe_intermediate_size, c.n_routed_experts,
            first=c.experts_first, count=c.experts_count,
            top_k=c.num_experts_per_tok, n_shared=c.n_shared_experts,
            scale=c.routed_scaling_factor, renormalize=c.norm_topk_prob,
            init_std=c.init_std, bias_range=c.router_bias_range,
            rows_factor=getattr(c, "expert_rows_factor", 2.0))

    # ------------------------------ params --------------------------------
    def _init_ends(self, k_embed, k_head) -> dict:
        """embed, head and final_ln."""
        c = self.c
        return {
            "embed": self.embed.init(k_embed, c.dtype),
            "head": {"weight": jax.random.normal(
                k_head, (c.vocab_size, c.hidden), c.dtype) * c.init_std},
            "final_ln": {"weight": jnp.ones((c.hidden,), c.dtype)},
        }

    def partition_specs(self):
        """PartitionSpec pytree matching init(): the vocabulary's rows
        over the tp axis (of size 1), everything else replicated."""
        c = self.c
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        specs = jax.tree.map(lambda _: P(), shapes)
        specs["embed"] = {"weight": P(c.axis_name, None)}
        specs["head"] = {"weight": P(c.axis_name, None)}
        return specs

    # ------------------------------ forward -------------------------------
    def _norm(self, p, x):
        return fused_rms_norm(x, p["weight"], eps=self.c.rms_norm_eps)

    def _dot(self, x, w):
        return jnp.dot(x, w, preferred_element_type=jnp.float32
                       ).astype(x.dtype)

    def _embed(self, params, ids):
        with jax.named_scope("embed"):
            return self.embed.apply(params["embed"], ids)

    def _final_ln(self, params, h):
        with jax.named_scope("final_ln"):
            return self._norm(params["final_ln"], h)

    def logits_local(self, params, h):
        """The untied head over the held rows: (B, S, V/tp)."""
        with jax.named_scope("head"):
            return self._head(params, h)

    def _head(self, params, h):
        out_dtype = self.c.logits_dtype or jnp.float32
        return jnp.einsum("bsh,vh->bsv", h, params["head"]["weight"],
                          preferred_element_type=jnp.float32
                          ).astype(out_dtype)

    def _xent(self, logits, labels):
        return vocab_parallel_cross_entropy(
            logits, labels, axis_name=self.c.axis_name,
            fused=self.c.fused_xent)

    @staticmethod
    def _counts(stats):
        """(counts (layers, experts_count) int32, overflow (layers,)
        int32) of the expert layers' HeldExpertsStats in layer order."""
        return (jnp.stack([s.counts for s in stats]),
                jnp.stack([s.overflow for s in stats]))
