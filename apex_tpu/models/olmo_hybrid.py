"""OlmoHybrid — a dense decoder whose layers mix Gated DeltaNet and
full softmax attention in one stack under OLMo's reordered norm, as one
pipeline stage of whole layers holds it (Olmo-Hybrid-7B's
`olmo_hybrid`): full attention on the layers `attention_layers` names,
Gated DeltaNet (Yang et al., arXiv:2412.06464, the form of FLA's
`GatedDeltaNet`) on the others, a dense SwiGLU in every layer.

The layers (no bias but the decay's `dt_bias`, RMSNorm, untied
embedding and head, no rotary embedding and no position table):

* block, post-normed (OLMo 2, arXiv:2501.00656): `h = x +
  RMSNorm(Mixer_i(x))`, `y = h + RMSNorm(FFN(h))`; no norm on a
  sublayer's input; a final RMSNorm before the head;
* full attention: `q = RMSNorm(x W_q)`, `k = RMSNorm(x W_k)`, each norm
  over the whole projection, `v = x W_v`; causal `softmax(q k^T /
  sqrt(d)) v` over `num_heads` heads of `head_dim`; `out = ctx W_o`;
* Gated DeltaNet, n heads, keys `gdn_key_dim` and values
  `gdn_value_dim` wide: `q, k, v = SiLU(conv(x W_.))`, a causal
  depthwise convolution over time, `conv_kernel` taps, no bias; a
  head's q and k of unit length, q by d_k^-1/2 more; **one log-decay a
  head and token** `g = -exp(A_log_h) softplus(x W_a + dt_bias)` in
  float32; `beta = sigmoid(x W_b)`, twice that where
  `allow_neg_eigval`; the state and the outputs by
  `ops.delta_rule.gated_delta_rule` with g of (B, n, S); `out = W_o
  [RMSNorm_dv(o) * SiLU(x W_g)]`, the norm's weight of d_v shared by
  the heads, W_g full rank;
* FFN: `W_2 (SiLU(W_1 h) * W_3 h)`, `intermediate_size` wide, from
  `HeldExpertsLM`, which holds no expert layer here (the config names
  no `n_routed_experts`): `routing_counts` are empty.

**Documents.**  With `eod_token_id` a row is documents packed end to
end, each closed by that id (`HeldExpertsLM.documents`, once a step,
under `block0/attn/segments`): the flash call takes them as segment
ids, a convolution tap that would reach into the document before reads
0, and the delta rule's state is zero before a document's first token.

The config says what is held here: how many layers and which attend,
how many rows of the vocabulary.  The layers left out lie on other
chips as pipeline stages, the rows left out on the chips that share the
vocabulary; this module has no code that stands in for either.  Runs
shard-local inside `shard_map` with the surface the step builder takes
(`init`, `partition_specs`, `trunk`, `token_losses`, `loss`,
`routing_counts`), tensor parallelism 1 only.

Scopes: the mixer under `block*/attn` (the delta rule's projections
`attn/qkv`, its staging `attn/conv`, decay and beta `attn/decay`, scan
`attn/scan`, gate `attn/gate`, norm and gating `attn/onorm`, output
`attn/proj`; attention's `attn/qkv`, `attn/qknorm`, `attn/flash`,
`attn/proj`), the post-norms `block*/ln1` and `block*/ln2`, the FFN
`block*/mlp`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.models.held_experts_lm import HeldExpertsLM
from apex_tpu.models.hybrid_moe import _keeps
from apex_tpu.ops.conv_stage import stage_conv_heads
from apex_tpu.ops.delta_rule import gated_delta_rule
from apex_tpu.parallel.mesh import TP_AXIS


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 25088          # rows of embedding and head held here
    hidden: int = 3840
    num_layers: int = 4              # layers held here
    attention_layers: Tuple[int, ...] = (3,)   # which of them attend
    num_heads: int = 30
    head_dim: int = 128
    gdn_heads: int = 30
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.float32
    logits_dtype: Any = None         # None keeps fp32 logits
    # True puts a `jax.checkpoint` around every mixer: of a delta-rule
    # mixer a step keeps its input and what `hybrid_moe.KEPT` names, of
    # a layer that attends everything (as `HybridMoE` does)
    recompute_mixers: bool = False
    scan_chunk: Optional[int] = None   # None: the tuner's, or the op's
    # the id that closes a document of a packed row; None: a row is one
    eod_token_id: Optional[int] = None
    # the dispatch of the Pallas kernels, as `flash_attention` takes it
    flash_override: Any = None
    fused_xent: Any = None
    axis_name: str = TP_AXIS


class OlmoHybrid(HeldExpertsLM):
    def _attends(self, i: int) -> bool:
        return i in self.c.attention_layers

    # ------------------------------ params --------------------------------
    def _init_block(self, key, i: int) -> dict:
        c = self.c
        ks = jax.random.split(key, 12)
        h = c.hidden

        def normal(k, *shape):
            return jax.random.normal(k, shape, c.dtype) * c.init_std

        def ones(n):
            return {"weight": jnp.ones((n,), c.dtype)}

        if self._attends(i):
            wide = c.num_heads * c.head_dim
            attn = {"q": normal(ks[0], h, wide), "k": normal(ks[1], h, wide),
                    "v": normal(ks[2], h, wide), "q_norm": ones(wide),
                    "k_norm": ones(wide), "proj": normal(ks[3], wide, h)}
        else:
            n = c.gdn_heads
            keys, values = n * c.gdn_key_dim, n * c.gdn_value_dim
            # A_h and dt_bias as FLA's GatedDeltaNet starts them: A
            # uniform in (0, 16), a time step log-uniform in (1e-3, 1e-1)
            # and the bias its inverse softplus
            dt = jnp.exp(jax.random.uniform(
                ks[4], (n,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            attn = {
                "q": normal(ks[0], h, keys), "k": normal(ks[1], h, keys),
                "v": normal(ks[2], h, values),
                # a tap's weight a channel, uniform in +-1/sqrt(taps) as
                # a depthwise convolution is started
                **{f"conv_{x}": jax.random.uniform(
                    k, (c.conv_kernel, width), c.dtype, -1.0, 1.0)
                   / math.sqrt(c.conv_kernel)
                   for x, k, width in zip("qkv", ks[5:8],
                                          (keys, keys, values))},
                "a": normal(ks[8], h, n),
                "a_log": jnp.log(jax.random.uniform(
                    ks[9], (n,), jnp.float32, jnp.finfo(jnp.float32).tiny,
                    16.0)).astype(c.dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(c.dtype),
                "beta": normal(ks[10], h, n),
                "gate": normal(ks[11], h, values),
                "o_norm": ones(c.gdn_value_dim),
                "proj": normal(ks[3], values, h),
            }
        k_up, k_down = jax.random.split(jax.random.fold_in(key, 1))
        mlp = {"gate_up": normal(k_up, h, 2 * c.intermediate_size),
               "down": normal(k_down, c.intermediate_size, h)}
        return {"attn": attn, "ln1": ones(h), "mlp": mlp, "ln2": ones(h)}

    # ------------------------------ forward -------------------------------
    def _attention(self, p, x, docs=None):
        """x: (B, S, H), the block's input.  Full attention's output,
        before its post-norm."""
        c = self.c
        q, k, v = self._qkv(p, x)
        with jax.named_scope("qknorm"):
            q, k = self._norm(p["q_norm"], q), self._norm(p["k_norm"], k)
        with jax.named_scope("flash"):
            ctx = self._attend(
                self._heads(q, c.num_heads), self._heads(k, c.num_heads),
                self._heads(v, c.num_heads),
                None if docs is None else docs.ids)
        with jax.named_scope("proj"):
            return self._dot(ctx, p["proj"])

    def scan_inputs(self, p, x, docs=None):
        """x: (B, S, H), the block's input.  What Gated DeltaNet hands
        `gated_delta_rule`, head-major: q, k (B, n, S, d_k), v (B, n, S,
        d_v) in the model's dtype, the log-decay g and beta (B, n, S) in
        float32.  `docs`: the row's `documents`, for the taps."""
        c = self.c
        f32 = jnp.float32
        with jax.named_scope("qkv"):
            q, k, v = (self._keep(self._dot(x, p[w]), f"kda_{w}")
                       for w in "qkv")
        with jax.named_scope("conv"):
            # q's and k's heads of unit length, q by d_k^-1/2 more
            q, k, v = (self._keep(t, "kda_staged") for t in stage_conv_heads(
                (q, k, v), [p[f"conv_{w}"] for w in "qkv"], c.gdn_heads,
                (c.gdn_key_dim ** -0.5, 1.0, None),
                ids=None if docs is None else docs.ids,
                masks=None if docs is None else docs.taps,
                use_pallas_override=c.flash_override))
        with jax.named_scope("decay"):
            rate = jnp.exp(p["a_log"].astype(f32))
            g = -rate * jax.nn.softplus(
                self._keep(jnp.dot(x, p["a"], preferred_element_type=f32),
                           "kda_f") + p["dt_bias"].astype(f32))
            beta = jax.nn.sigmoid(self._keep(jnp.dot(
                x, p["beta"], preferred_element_type=f32), "kda_beta"))
            if c.allow_neg_eigval:
                beta = 2.0 * beta
        return q, k, v, g.transpose(0, 2, 1), beta.transpose(0, 2, 1)

    def _gdn(self, p, x, docs=None):
        """x: (B, S, H), the block's input.  Gated DeltaNet's output,
        before its post-norm."""
        c = self.c
        b, s, _ = x.shape
        n, dv = c.gdn_heads, c.gdn_value_dim
        f32 = jnp.float32
        q, k, v, g, beta = self.scan_inputs(p, x, docs)
        with jax.named_scope("scan"):
            o = self._keep(gated_delta_rule(
                q, k, v, g, beta, chunk=c.scan_chunk,
                resets=None if docs is None else docs.first), "kda_o")
        with jax.named_scope("gate"):
            gate = self._keep(self._dot(x, p["gate"]), "kda_g")
        with jax.named_scope("onorm"):
            o = o.transpose(0, 2, 1, 3).astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + c.rms_norm_eps)
            o = (o * p["o_norm"]["weight"].astype(f32)).reshape(b, s, n * dv)
            o = self._keep((o * jax.nn.silu(gate.astype(f32))).astype(c.dtype),
                           "kda_gated")
        with jax.named_scope("proj"):
            return self._dot(o, p["proj"])

    def _block(self, i, p, x, docs=None):
        """Post-normed: the mixer and the FFN read the residual stream
        as it is, and each output is normed before it is added.  Under
        `recompute_mixers` a delta-rule mixer keeps its input and what
        `hybrid_moe.KEPT` names, a layer that attends keeps everything,
        by the policies `HybridMoE._block` gives its own two kinds."""
        attends = self._attends(i)
        mixer = self._attention if attends else self._gdn
        if self.c.recompute_mixers:
            mixer = jax.checkpoint(
                mixer, policy=jax.checkpoint_policies.everything_saveable
                if attends else _keeps)
        with jax.named_scope(f"block{i}"):
            with jax.named_scope("attn"):
                y = mixer(p["attn"], x, docs)
            with jax.named_scope("ln1"):
                x = x + self._norm(p["ln1"], y)
            with jax.named_scope("mlp"):
                y = self._swiglu(p["mlp"], x)
            with jax.named_scope("ln2"):
                return x + self._norm(p["ln2"], y)

    def trunk(self, params, tokens):
        """tokens (B, S) -> (the residual stream after the last held
        layer, (B, S, H), before the final norm; [], no expert layer)."""
        h = self._embed(params, tokens)
        docs = self.documents(tokens)
        for i in range(self.c.num_layers):
            h = self._block(i, params[f"block{i}"], h, docs)
        return h, []
