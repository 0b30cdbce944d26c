"""BERT — bidirectional tensor-parallel encoder (MLM + NSP).

≡ the reference's standalone BERT
(apex/transformer/testing/standalone_bert.py over
standalone_transformer_lm.py): token+position+tokentype embeddings,
padding-masked attention (FusedScaleMaskSoftmax padding variant), TP
transformer blocks, pooler, tied-weight MLM head and binary NSP head.
Pairs with FusedLAMB for the BERT-Large pretraining baseline config
(BASELINE.md).

Layout (S, B, H) like the GPT flagship; shard-local inside shard_map.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.ops._common import tap as _tap
from apex_tpu.ops.layer_norm import fused_layer_norm
from apex_tpu.ops.softmax import scaled_masked_softmax
from apex_tpu.parallel.mesh import TP_AXIS
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30528
    seq_len: int = 512
    hidden: int = 1024          # BERT-Large defaults
    num_layers: int = 24
    num_heads: int = 16
    ffn_mult: int = 4
    num_tokentypes: int = 2
    dtype: Any = jnp.float32
    # MLM logits dtype: None keeps fp32 (S, B, V) logits; bf16 halves
    # their fwd+bwd HBM traffic (the xent upcasts internally either
    # way) — same contract as GPTConfig.logits_dtype
    logits_dtype: Any = None
    # padding-masked FLASH attention (segment-id masked Pallas kernel)
    # instead of the dense FusedScaleMaskSoftmax path: no S^2 score
    # matrix, so BERT trains at seq 4k+ on one chip (VERDICT r1 #3)
    use_flash_attention: bool = False
    axis_name: str = TP_AXIS

    @property
    def head_dim(self):
        return self.hidden // self.num_heads


class Bert:
    def __init__(self, config: BertConfig):
        self.c = c = config
        self.embed = VocabParallelEmbedding(c.vocab_size, c.hidden,
                                            axis_name=c.axis_name)
        self.blocks = []
        for _ in range(c.num_layers):
            self.blocks.append((
                ColumnParallelLinear(c.hidden, 3 * c.hidden,
                                     gather_output=False,
                                     axis_name=c.axis_name, init_std=0.02),
                RowParallelLinear(c.hidden, c.hidden, input_is_parallel=True,
                                  axis_name=c.axis_name,
                                  init_std=0.02 / math.sqrt(2 * c.num_layers)),
                ColumnParallelLinear(c.hidden, c.ffn_mult * c.hidden,
                                     gather_output=False,
                                     axis_name=c.axis_name, init_std=0.02),
                RowParallelLinear(c.ffn_mult * c.hidden, c.hidden,
                                  input_is_parallel=True,
                                  axis_name=c.axis_name,
                                  init_std=0.02 / math.sqrt(2 * c.num_layers)),
            ))

    def init(self, key):
        c = self.c
        ks = jax.random.split(key, 6 + 4 * c.num_layers)
        params = {
            "embed": self.embed.init(ks[0], c.dtype),
            "pos_embed": jax.random.normal(ks[1], (c.seq_len, c.hidden),
                                           c.dtype) * 0.02,
            "tokentype_embed": jax.random.normal(
                ks[2], (c.num_tokentypes, c.hidden), c.dtype) * 0.02,
            "embed_ln": {"weight": jnp.ones((c.hidden,), c.dtype),
                         "bias": jnp.zeros((c.hidden,), c.dtype)},
            "pooler_w": jax.random.normal(ks[3], (c.hidden, c.hidden),
                                          c.dtype) * 0.02,
            "pooler_b": jnp.zeros((c.hidden,), c.dtype),
            "lm_head_ln": {"weight": jnp.ones((c.hidden,), c.dtype),
                           "bias": jnp.zeros((c.hidden,), c.dtype)},
            "lm_head_dense_w": jax.random.normal(
                ks[4], (c.hidden, c.hidden), c.dtype) * 0.02,
            "lm_head_dense_b": jnp.zeros((c.hidden,), c.dtype),
            "nsp_w": jax.random.normal(ks[5], (c.hidden, 2), c.dtype) * 0.02,
            "nsp_b": jnp.zeros((2,), c.dtype),
        }
        for i, mods in enumerate(self.blocks):
            k = jax.random.split(ks[5], 4 * c.num_layers)[4 * i: 4 * i + 4]
            params[f"block{i}"] = {
                "ln1": {"weight": jnp.ones((c.hidden,), c.dtype),
                        "bias": jnp.zeros((c.hidden,), c.dtype)},
                "qkv": mods[0].init(k[0], c.dtype),
                "proj": mods[1].init(k[1], c.dtype),
                "ln2": {"weight": jnp.ones((c.hidden,), c.dtype),
                        "bias": jnp.zeros((c.hidden,), c.dtype)},
                "fc1": mods[2].init(k[2], c.dtype),
                "fc2": mods[3].init(k[3], c.dtype),
            }
        return params

    def partition_specs(self):
        c = self.c
        col = {"weight": P(None, c.axis_name), "bias": P(c.axis_name)}
        row = {"weight": P(c.axis_name, None), "bias": P()}
        ln = {"weight": P(), "bias": P()}
        specs = {
            "embed": {"weight": P(c.axis_name, None)},
            "pos_embed": P(), "tokentype_embed": P(), "embed_ln": dict(ln),
            "pooler_w": P(), "pooler_b": P(),
            "lm_head_ln": dict(ln), "lm_head_dense_w": P(),
            "lm_head_dense_b": P(), "nsp_w": P(), "nsp_b": P(),
        }
        for i in range(c.num_layers):
            specs[f"block{i}"] = {"ln1": dict(ln), "qkv": dict(col),
                                  "proj": dict(row), "ln2": dict(ln),
                                  "fc1": dict(col), "fc2": dict(row)}
        return specs

    def _attention(self, bp, qkv_mod, proj_mod, x, pad_mask):
        c = self.c
        qkv = qkv_mod.apply(bp["qkv"], x)   # (S, B, 3H/tp)
        s, b, _ = qkv.shape
        nh_local = qkv.shape[-1] // (3 * c.head_dim)
        qkv = qkv.reshape(s, b, 3, nh_local, c.head_dim)
        q, k, v = (qkv[:, :, i].transpose(1, 2, 0, 3) for i in range(3))
        if c.use_flash_attention:
            # pad_mask (B, S) True = padded → segment ids: real tokens
            # share one id, pads another, so cross attention is masked
            # without ever materializing the S^2 scores
            from apex_tpu.ops.flash_attention import flash_attention
            seg = jnp.logical_not(pad_mask).astype(jnp.int32)
            ctx = flash_attention(q, k, v,
                                  softmax_scale=1.0 / math.sqrt(c.head_dim),
                                  segment_ids=seg).astype(x.dtype)
        else:
            scores = jnp.einsum("bnsh,bnth->bnst", q, k,
                                preferred_element_type=jnp.float32
                                ).astype(x.dtype)
            # pad_mask: (B, S) True = padded → mask (B, 1, S, S)
            mask = pad_mask[:, None, None, :]
            probs = scaled_masked_softmax(scores, mask,
                                          1.0 / math.sqrt(c.head_dim))
            ctx = jnp.einsum("bnst,bnth->bnsh", probs, v,
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, -1)
        return proj_mod.apply(bp["proj"], ctx)

    def encode(self, params, tokens, tokentype_ids=None, pad_mask=None):
        """tokens: (B, S) → hidden (S, B, H)."""
        c = self.c
        ids = tokens.T
        h = self.embed.apply(params["embed"], ids)
        h = h + params["pos_embed"][: ids.shape[0]][:, None, :].astype(h.dtype)
        if tokentype_ids is not None:
            tt = jnp.take(params["tokentype_embed"], tokentype_ids.T, axis=0)
            h = h + tt.astype(h.dtype)
        h = fused_layer_norm(h, params["embed_ln"]["weight"],
                             params["embed_ln"]["bias"])
        if pad_mask is None:
            pad_mask = jnp.zeros(tokens.shape, bool)
        # `_tap` points mirror GPT._block (flight-recorder stat taps):
        # identity no-ops unless a TapContext is active
        for i, mods in enumerate(self.blocks):
            bp = params[f"block{i}"]
            hn = fused_layer_norm(h, bp["ln1"]["weight"], bp["ln1"]["bias"])
            hn = _tap(hn, f"block{i}/ln1")
            h = h + _tap(self._attention(bp, mods[0], mods[1], hn, pad_mask),
                         f"block{i}/attn")
            hn = fused_layer_norm(h, bp["ln2"]["weight"], bp["ln2"]["bias"])
            hn = _tap(hn, f"block{i}/ln2")
            m = mods[2].apply(bp["fc1"], hn)
            m = jax.nn.gelu(m, approximate=True)
            h = h + _tap(mods[3].apply(bp["fc2"], m), f"block{i}/mlp")
        return h

    def loss(self, params, tokens, mlm_labels, loss_mask,
             nsp_labels=None, tokentype_ids=None, pad_mask=None):
        """Masked-LM loss (+ NSP when labels given) ≡ standalone BERT's
        pretraining loss (standalone_bert.py forward)."""
        c = self.c
        h = self.encode(params, tokens, tokentype_ids, pad_mask)
        # MLM head: dense+gelu+LN then tied-embedding projection
        lm = h @ params["lm_head_dense_w"].astype(h.dtype) + \
            params["lm_head_dense_b"].astype(h.dtype)
        lm = jax.nn.gelu(lm, approximate=True)
        lm = fused_layer_norm(lm, params["lm_head_ln"]["weight"],
                              params["lm_head_ln"]["bias"])
        from apex_tpu.parallel.collectives import (
            copy_to_tensor_model_parallel_region)
        lm = copy_to_tensor_model_parallel_region(lm, c.axis_name)
        logits = jnp.einsum("sbh,vh->sbv", lm,
                            params["embed"]["weight"],
                            preferred_element_type=jnp.float32
                            ).astype(c.logits_dtype or jnp.float32)
        per_tok = vocab_parallel_cross_entropy(logits, mlm_labels.T,
                                               axis_name=c.axis_name)
        lm_mask = loss_mask.T.astype(jnp.float32)
        mlm_loss = jnp.sum(per_tok * lm_mask) / jnp.maximum(
            jnp.sum(lm_mask), 1.0)
        if nsp_labels is None:
            return mlm_loss
        pooled = jnp.tanh(h[0] @ params["pooler_w"].astype(h.dtype)
                          + params["pooler_b"].astype(h.dtype))  # (B, H)
        nsp_logits = pooled @ params["nsp_w"].astype(h.dtype) + \
            params["nsp_b"].astype(h.dtype)
        nsp = jnp.mean(
            -jax.nn.log_softmax(nsp_logits.astype(jnp.float32))[
                jnp.arange(nsp_logits.shape[0]), nsp_labels])
        return mlm_loss + nsp


def bert_large(**overrides) -> Bert:
    return Bert(BertConfig(**overrides))
