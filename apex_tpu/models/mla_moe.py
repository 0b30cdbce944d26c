"""MLAMoE — the DeepSeek-V3 family's block, as one chip of an
expert-parallel group holds it: latent attention, a sigmoid-routed
fine-grained expert layer with a shared expert, multi-token prediction.

The layers, by the keys of the family's `config.json` (no bias
anywhere, RMSNorm, untied embedding and head, no position table):

* block: `h += MLA(RMSNorm(h))`, `h += FFN(RMSNorm(h))`;
* MLA: `c_q = RMSNorm(a W_qa)`; `q = c_q W_qb`, per head (nope | rope);
  `[c_kv | k_r] = a W_kva`, `c_kv = RMSNorm(c_kv)`; `[k_nope | v] =
  c_kv W_kvb` per head; interleaved RoPE on `q_r` and on the one `k_r`
  all heads share; causal softmax attention with keys of
  `qk_nope + qk_rope` and values of `v_head_dim`
  (`ops.flash_attention.flash_attention` takes the two widths apart);
  the output projection.  The parameters keep the published column
  order; how the projections' outputs reach the kernels is
  `_attention`'s business (`ops.rope_stage`);
* FFN of the leading `first_k_dense_replace` layers: a SwiGLU of
  `intermediate_size`; of the others: `moe.HeldExpertsMLP`, which
  routes over all `n_routed_experts` and computes the part of the
  result that experts `[experts_first, experts_first + experts_count)`
  give, plus the shared expert;
* multi-token prediction (arXiv:2412.19437 section 2.2), depth 1:
  `h' = W_eh [RMSNorm(h_i) ; RMSNorm(E[t_{i+1}])]`, one more expert
  block, a norm, the main model's embedding and head, cross entropy
  against `t_{i+2}`; `loss = L_main + mtp_lambda * L_mtp`.

The config says what is held here: how many leading dense and expert
layers, whether the MTP module, which experts, how many rows of the
vocabulary.  The layers left out lie on other chips as pipeline
stages, the experts left out on the other chips of the expert-parallel
group; this module has no code that stands in for either.

Runs shard-local inside `shard_map` over the (pp, dp, tp) mesh like
`models.gpt.GPT`, with `init`, `partition_specs`, `apply`,
`logits_local` and `loss` of the same meaning, so
`make_tp_dp_train_step` drives it unchanged.  Tensor parallelism 1
only: the model's parallel axis is the experts', told by the config.
Activations are (B, S, H).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from apex_tpu.models.held_experts_lm import HeldExpertsLM
from apex_tpu.ops.rope_stage import (
    halves,
    rope_tables,
    stage_heads,
    turn_halves,
)
from apex_tpu.parallel.mesh import TP_AXIS


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    vocab_size: int = 16256          # rows of embedding and head held here
    hidden: int = 2048
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168    # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256      # the router's width, as published
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rope_theta: float = 32e6
    rms_norm_eps: float = 1e-6
    # what this chip holds
    first_k_dense_replace: int = 1   # leading dense layers
    num_expert_layers: int = 4       # expert layers after them
    mtp: bool = True                 # the multi-token-prediction module
    experts_first: int = 0           # experts [first, first + count)
    experts_count: int = 16
    mtp_lambda: float = 0.3
    init_std: float = 0.02
    router_bias_range: float = 0.05  # the seeded, fixed e_score_correction_bias
    dtype: Any = jnp.float32
    logits_dtype: Any = None         # None keeps fp32 logits
    # the dispatch of the attention's kernels (flash and the staging
    # pass in front of it), as `flash_attention` takes it: None lets the
    # backend decide (the kernels on a TPU, the jnp references
    # elsewhere), True forces the kernels (interpreted off the chip),
    # False the jnp references
    flash_override: Any = None
    fused_xent: Any = None
    axis_name: str = TP_AXIS

    @property
    def num_layers(self) -> int:
        return self.first_k_dense_replace + self.num_expert_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


class MLAMoE(HeldExpertsLM):
    """The ends of the network, the expert layer and the small pieces
    of a block are `HeldExpertsLM`'s, shared with `models.hybrid_moe`."""

    def __init__(self, config: MLAMoEConfig):
        super().__init__(config)
        # block indices: the layers held, then the MTP module's own
        self.n_blocks = config.num_layers + int(config.mtp)

    def _is_dense(self, i: int) -> bool:
        return i < self.c.first_k_dense_replace

    # ------------------------------ params --------------------------------
    def _init_block(self, key, i: int) -> dict:
        c = self.c
        ks = jax.random.split(key, 8)
        nh, h = c.num_heads, c.hidden

        def normal(k, *shape):
            return jax.random.normal(k, shape, c.dtype) * c.init_std

        def ones(n):
            return {"weight": jnp.ones((n,), c.dtype)}

        attn = {
            "q_a": normal(ks[0], h, c.q_lora_rank),
            "q_a_norm": ones(c.q_lora_rank),
            "q_b": normal(ks[1], c.q_lora_rank, nh * c.qk_head_dim),
            "kv_a": normal(ks[2], h, c.kv_lora_rank + c.qk_rope_head_dim),
            "kv_a_norm": ones(c.kv_lora_rank),
            "kv_b": normal(ks[3], c.kv_lora_rank,
                           nh * (c.qk_nope_head_dim + c.v_head_dim)),
            "proj": normal(ks[4], nh * c.v_head_dim, h),
        }
        if self._is_dense(i):
            mlp = {"gate_up": normal(ks[5], h, 2 * c.intermediate_size),
                   "down": normal(ks[6], c.intermediate_size, h)}
        else:
            mlp = self.experts.init(ks[7], c.dtype)
        return {"ln1": ones(h), "attn": attn, "ln2": ones(h), "mlp": mlp}

    def init(self, key):
        c = self.c
        keys = jax.random.split(key, 3 + self.n_blocks)
        params = self._init_ends(keys[0], keys[1])
        for i in range(self.n_blocks):
            params[f"block{i}"] = self._init_block(keys[3 + i], i)
        if c.mtp:
            params["mtp"] = {
                "hnorm": {"weight": jnp.ones((c.hidden,), c.dtype)},
                "enorm": {"weight": jnp.ones((c.hidden,), c.dtype)},
                "proj": jax.random.normal(
                    keys[2], (2 * c.hidden, c.hidden), c.dtype) * c.init_std,
                "final_ln": {"weight": jnp.ones((c.hidden,), c.dtype)},
            }
        return params

    # ------------------------------ forward -------------------------------
    def _tables(self, i, seq):
        """The rotary tables (cos, sin), each (seq, qk_rope / 2) fp32.
        Whoever runs the blocks computes them once and hands them to
        every block; the time is filed under block i, the first it
        runs."""
        with jax.named_scope(f"block{i}"), jax.named_scope("attn"), \
                jax.named_scope("rope"):
            return rope_tables(seq, self.c.qk_rope_head_dim,
                               self.c.rope_theta)

    def _attention(self, p, a, tables):
        """a: (B, S, H), normed; tables: `_tables`.  The latent
        attention's output, before the residual add.

        The weights pair rotary lanes (2i, 2i + 1); here the rotary
        columns of `q_b` and `kv_a` are taken in halves order (a gather
        on megabytes of weight, whose transpose carries the gradient
        back), so that q's and k's rotary lanes share an order and a
        rotation is two multiplies and an add a half.  `q_b` and `kv_b`
        each run as two GEMMs over their column groups, so that every
        output lies as its one reader takes it: `stage_heads` turns q's
        rotary lanes and writes q and k head-major, k's shared rotary
        row once a head, in one pass each; v and the context cross
        between the token-major GEMMs and the head-major kernels in one
        copy each.  The key-value side, the flash call and the output
        projection are `HeldExpertsLM`'s, shared with the latent layers
        of `models.hybrid_moe`."""
        c = self.c
        nh, dn, dr = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
        with jax.named_scope("q_a"):
            c_q = self._norm(p["q_a_norm"], self._dot(a, p["q_a"]))
        with jax.named_scope("q_b"):
            w = p["q_b"].reshape(-1, nh, dn + dr)
            q_n = self._dot(c_q, w[..., :dn].reshape(-1, nh * dn))
            q_r = self._dot(c_q, halves(w[..., dn:]).reshape(-1, nh * dr))
        k_n, k_r, v = self._latent_kv(p, a, rotary=True)
        with jax.named_scope("rope"):
            q = stage_heads(q_n, q_r, nh, tables,
                            use_pallas_override=c.flash_override)
            k = stage_heads(k_n, turn_halves(k_r, *tables), nh,
                            use_pallas_override=c.flash_override)
        return self._latent_attend(p, q, k, v)

    def _mlp(self, i, p, m):
        """(the FFN's output, HeldExpertsStats or None)."""
        if self._is_dense(i):
            return self._swiglu(p, m), None
        return self.experts.apply(p, m)

    def _block(self, i, p, x, tables):
        with jax.named_scope(f"block{i}"):
            with jax.named_scope("ln1"):
                a = self._norm(p["ln1"], x)
            with jax.named_scope("attn"):
                x = x + self._attention(p["attn"], a, tables)
            with jax.named_scope("ln2"):
                m = self._norm(p["ln2"], x)
            with jax.named_scope("mlp"):
                y, stats = self._mlp(i, p["mlp"], m)
                return x + y, stats

    def trunk(self, params, tokens, tables=None):
        """tokens (B, S) -> (the residual stream after the last held
        layer, (B, S, H), before the final norm; the expert layers'
        HeldExpertsStats in layer order).  `tables`: `_tables`, where
        the caller has them."""
        h = self._embed(params, tokens)
        if tables is None:
            tables = self._tables(0, tokens.shape[1])
        stats = []
        for i in range(self.c.num_layers):
            h, st = self._block(i, params[f"block{i}"], h, tables)
            if st is not None:
                stats.append(st)
        return h, stats

    def apply(self, params, tokens, key=None):
        """tokens: (B, S) ids within the held rows.  The hidden states
        the head reads, (B, S, H).  Shard-local: call inside
        shard_map."""
        h, _ = self.trunk(params, tokens)
        return self._final_ln(params, h)

    def mtp_hidden(self, params, h, next_tokens, tables=None):
        """The MTP module up to its own final norm: `h` the trunk's
        residual stream (B, S, H), `next_tokens` (B, S) the token after
        each position, `tables` the trunk's `_tables` where the caller
        has them.  Returns (hidden the shared head reads, the MTP
        block's HeldExpertsStats)."""
        p = params["mtp"]
        with jax.named_scope("mtp"):
            with jax.named_scope("proj"):
                # embed's own scope is the main model's: this lookup
                # is the module's
                e = self.embed.apply(params["embed"], next_tokens)
                both = jnp.concatenate(
                    [self._norm(p["hnorm"], h), self._norm(p["enorm"], e)],
                    axis=-1)
                x = self._dot(both, p["proj"])
        i = self.c.num_layers
        if tables is None:
            tables = self._tables(i, h.shape[1])
        x, stats = self._block(i, params[f"block{i}"], x, tables)
        with jax.named_scope("mtp"):
            return self._norm(p["final_ln"], x), stats

    def token_losses(self, params, tokens, labels):
        """(main, mtp, stats): per-token cross entropies (B, S) fp32 of
        the main head against `labels` and of the MTP head against the
        labels one further on (`labels` rolled by one: a sequence's
        last position is given its first label, as the benchmark's
        seeded batches give the main head), or None without the
        module; and every expert layer's HeldExpertsStats."""
        tables = self._tables(0, tokens.shape[1])
        h, stats = self.trunk(params, tokens, tables)
        logits = self.logits_local(params, self._final_ln(params, h))
        with jax.named_scope("loss"):
            main = self._xent(logits, labels)
        if not self.c.mtp:
            return main, None, stats
        hm, st = self.mtp_hidden(params, h, labels, tables)
        with jax.named_scope("mtp"), jax.named_scope("head"):
            mtp = self._xent(self._head(params, hm),
                             jnp.roll(labels, -1, axis=1))
        return main, mtp, stats + [st]

    def loss(self, params, tokens, labels, key=None):
        """`L_main + mtp_lambda * L_mtp`, each the mean over tokens.
        tokens/labels: (B, S)."""
        main, mtp, _ = self.token_losses(params, tokens, labels)
        with jax.named_scope("loss"):
            total = jnp.mean(main)
            if mtp is not None:
                total = total + self.c.mtp_lambda * jnp.mean(mtp)
            return total

    def routing_counts(self, params, tokens, labels):
        """Forward only, without the heads: (counts (layers,
        experts_count) int32, overflow (layers,) int32) of every expert
        layer held, the MTP block's last: what a router-bias update
        reads, and whether the grouped buffers' bound held."""
        tables = self._tables(0, tokens.shape[1])
        h, stats = self.trunk(params, tokens, tables)
        if self.c.mtp:
            stats = stats + [self.mtp_hidden(params, h, labels, tables)[1]]
        return self._counts(stats)
