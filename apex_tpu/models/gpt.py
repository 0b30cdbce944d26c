"""GPT — tensor/sequence-parallel transformer LM, the flagship model.

≡ the reference's standalone Megatron GPT
(apex/transformer/testing/standalone_transformer_lm.py, 1574 LoC;
standalone_gpt.py:33-50) re-designed TPU-first:

* layout (S, B, H) so sequence-parallel collectives act on dim 0 (same
  choice as Megatron, and contiguous for TPU lane tiling);
* attention QKV via ColumnParallelLinear (heads sharded over tp),
  causal Pallas softmax (or flash attention, ops/flash_attention.py),
  output via RowParallelLinear;
* MLP = ColumnParallel → gelu → RowParallel (4x hidden);
* vocab-parallel embedding + tied-weight LM head + vocab-parallel
  cross entropy;
* runs shard-local inside `shard_map` over the (pp, dp, tp) mesh —
  partition_specs() gives every param its PartitionSpec.

Dropout uses functional keys (fold_in per layer and per tp rank ≡ the
CudaRNGStatesTracker contract, tensor_parallel/random.py:204-235).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from apex_tpu.ops._common import tap as _tap
from apex_tpu.ops.layer_norm import fused_layer_norm
from apex_tpu.ops.softmax import scaled_upper_triang_masked_softmax
from apex_tpu.parallel.collectives import (
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    scatter_to_sequence_parallel_region,
)
from apex_tpu.parallel.mesh import TP_AXIS
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from apex_tpu.transformer.tensor_parallel.random import (
    model_parallel_fold_in,
)

# The checkpoint_name tags _block emits — the single source of truth
# shared by the block (via _cn below) and remat_policy validation.
REMAT_TAGS = frozenset({"qkv", "attn_ctx", "attn_out", "ffn1", "ffn_out"})


def _cn(x, name):
    assert name in REMAT_TAGS, name  # keep REMAT_TAGS in sync with _block
    return checkpoint_name(x, name)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    seq_len: int = 1024
    hidden: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    ffn_mult: int = 4
    dropout: float = 0.0
    dtype: Any = jnp.float32
    # LM-head logits dtype: None keeps fp32 logits.  bf16 halves the
    # (S, B, V) HBM traffic in fwd and bwd; the cross entropy upcasts to
    # fp32 internally either way (≡ the reference xentropy_cuda, which
    # consumes fp16 logits with fp32 internal math).  Opt-in so existing
    # bf16 configs keep their fp32-logits numerics.
    logits_dtype: Any = None
    sequence_parallel: bool = False
    use_flash_attention: bool = False
    # Chunked compute/collective overlap depth for the TP layers
    # (parallel/overlap.py) and the MoE micro-chunk exchange.  None =
    # tuner-owned (`overlap_chunks` op, heuristic 1 — the monolithic
    # pre-overlap program, byte-identical on untuned machines); an int
    # forces the pipeline depth for A/B sweeps (non-dividing requests
    # fall back to the largest dividing count, warn once).
    overlap_chunks: Any = None
    remat: bool = False            # activation checkpointing per block
    # What the per-block checkpoint may keep (≡ the reference's partial /
    # selective activation checkpointing, fwd_bwd_pipelining_without_
    # interleaving.py:351-362 + tensor_parallel/random.py:237-306):
    #   None    — save nothing, recompute the whole block (full remat)
    #   "dots"  — save matmul (MXU) outputs, recompute elementwise only
    #   "names:a,b" — save only the listed checkpoint_name'd tensors
    #     (qkv, attn_ctx, attn_out, ffn1, ffn_out — see _block); the
    #     memory/recompute dial between full remat and "dots"
    remat_policy: Any = None
    # Vocab-parallel cross-entropy backward strategy: None = auto (the
    # fused custom_vjp when logits are sub-fp32, saving compute-dtype
    # residuals instead of the fp32 (S, B, V) upcast — cross_entropy.py
    # module docstring); True/False force it for A/B sweeps.
    fused_xent: Any = None
    axis_name: str = TP_AXIS

    @property
    def head_dim(self):
        return self.hidden // self.num_heads


# preset sizes ≡ gpt_scaling_test.py sweep points
GPT2_350M = dict(hidden=1024, num_layers=24, num_heads=16)
GPT2_1p3B = dict(hidden=2048, num_layers=24, num_heads=32)


class GPT:
    def __init__(self, config: GPTConfig):
        self.c = config
        c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden, axis_name=c.axis_name,
            sequence_parallel=c.sequence_parallel)
        self.blocks = []
        for _ in range(c.num_layers):
            qkv = ColumnParallelLinear(
                c.hidden, 3 * c.hidden, gather_output=False,
                sequence_parallel=c.sequence_parallel,
                axis_name=c.axis_name, init_std=0.02,
                overlap_chunks=c.overlap_chunks)
            proj = RowParallelLinear(
                c.hidden, c.hidden, input_is_parallel=True,
                sequence_parallel=c.sequence_parallel,
                axis_name=c.axis_name,
                init_std=0.02 / jnp.sqrt(2.0 * c.num_layers),
                overlap_chunks=c.overlap_chunks)
            fc1 = ColumnParallelLinear(
                c.hidden, c.ffn_mult * c.hidden, gather_output=False,
                sequence_parallel=c.sequence_parallel,
                axis_name=c.axis_name, init_std=0.02,
                overlap_chunks=c.overlap_chunks)
            fc2 = RowParallelLinear(
                c.ffn_mult * c.hidden, c.hidden, input_is_parallel=True,
                sequence_parallel=c.sequence_parallel,
                axis_name=c.axis_name,
                init_std=0.02 / jnp.sqrt(2.0 * c.num_layers),
                overlap_chunks=c.overlap_chunks)
            self.blocks.append((qkv, proj, fc1, fc2))

    # ------------------------------ params --------------------------------
    def init(self, key):
        c = self.c
        keys = jax.random.split(key, 2 + 4 * c.num_layers)
        params = {
            "embed": self.embed.init(keys[0], c.dtype),
            "pos_embed": jax.random.normal(
                keys[1], (c.seq_len, c.hidden), c.dtype) * 0.02,
            "final_ln": {"weight": jnp.ones((c.hidden,), c.dtype),
                         "bias": jnp.zeros((c.hidden,), c.dtype)},
        }
        for i, (qkv, proj, fc1, fc2) in enumerate(self.blocks):
            k = keys[2 + 4 * i: 6 + 4 * i]
            params[f"block{i}"] = {
                "ln1": {"weight": jnp.ones((c.hidden,), c.dtype),
                        "bias": jnp.zeros((c.hidden,), c.dtype)},
                "qkv": qkv.init(k[0], c.dtype),
                "proj": proj.init(k[1], c.dtype),
                "ln2": {"weight": jnp.ones((c.hidden,), c.dtype),
                        "bias": jnp.zeros((c.hidden,), c.dtype)},
                "fc1": fc1.init(k[2], c.dtype),
                "fc2": fc2.init(k[3], c.dtype),
            }
        return params

    def partition_specs(self):
        """PartitionSpec pytree matching init() — the TP sharding map
        (≡ the tensor_model_parallel param attributes, layers.py:70-107)."""
        c = self.c
        specs = {
            "embed": {"weight": P(c.axis_name, None)},
            "pos_embed": P(),
            "final_ln": {"weight": P(), "bias": P()},
        }
        col = {"weight": P(None, c.axis_name), "bias": P(c.axis_name)}
        row = {"weight": P(c.axis_name, None), "bias": P()}
        for i in range(c.num_layers):
            specs[f"block{i}"] = {
                "ln1": {"weight": P(), "bias": P()},
                "qkv": dict(col), "proj": dict(row),
                "ln2": {"weight": P(), "bias": P()},
                "fc1": dict(col), "fc2": dict(row),
            }
        return specs

    # ------------------------------ forward -------------------------------
    def _ln(self, p, x):
        w, b = p["weight"], p["bias"]
        if self.c.sequence_parallel:
            w = copy_to_tensor_model_parallel_region(w, self.c.axis_name)
            b = copy_to_tensor_model_parallel_region(b, self.c.axis_name)
        return fused_layer_norm(x, w, b)

    def _dropout(self, key, x):
        from apex_tpu.ops._common import dropout
        return dropout(key, self.c.dropout, x)

    def _attention(self, block_params, qkv_mod, proj_mod, x, key):
        """x: (S[, /tp], B, H) local.  Heads sharded over tp."""
        with jax.named_scope("qkv"):
            qkv = qkv_mod.apply(block_params["qkv"], x)  # (S, B, 3H/tp)
            qkv = _cn(qkv, "qkv")
        # whatever stands between the two GEMMs and the kernels (a
        # layout copy, the join of dq, dk and dv) is part of the
        # kernels' price, so it carries their scope
        with jax.named_scope("flash"):
            ctx = _cn(self._attention_core(qkv, key, x.dtype), "attn_ctx")
        with jax.named_scope("proj"):
            return proj_mod.apply(block_params["proj"], ctx)

    def _attention_core(self, qkv, key, dtype):
        """Causal attention over the packed (S, B, 3H/tp) projection:
        the context, (S, B, H/tp), where the output GEMM reads it."""
        c = self.c
        s, b, _ = qkv.shape
        nh_local = qkv.shape[-1] // (3 * c.head_dim)
        if c.use_flash_attention:
            # the kernels read the projection where it lies when its
            # shape allows (head pairs at head_dim 64), and a split and
            # transposed copy of it otherwise: the op decides from the
            # shapes it is handed
            from apex_tpu.ops.flash_attention import flash_attention_qkv
            rate = c.dropout if key is not None else 0.0
            return flash_attention_qkv(
                qkv, nh_local, causal=True,
                softmax_scale=1.0 / math.sqrt(c.head_dim),
                dropout_rate=rate, dropout_key=key if rate > 0 else None)
        # one transpose of the PACKED tensor instead of three strided
        # slice+transpose copies (ops/fused_dense.qkv_split_heads)
        from apex_tpu.ops.fused_dense import qkv_split_heads
        q, k, v = qkv_split_heads(qkv, nh_local, c.head_dim)
        scores = jnp.einsum("bnsh,bnth->bnst", q, k,
                            preferred_element_type=jnp.float32
                            ).astype(dtype)
        probs = scaled_upper_triang_masked_softmax(
            scores.reshape(-1, s, s),
            1.0 / math.sqrt(c.head_dim)).reshape(scores.shape)
        probs = self._dropout(key, probs)
        ctx = jnp.einsum("bnst,bnth->bnsh", probs, v,
                         preferred_element_type=jnp.float32).astype(dtype)
        return ctx.transpose(2, 0, 1, 3).reshape(s, b, -1)

    def _block(self, i, params, x, key):
        # `_tap` points (flight-recorder stat taps, monitor.trace): the
        # per-block ln/attn/mlp outputs, identity no-ops unless a
        # TapContext is active (ops._common.tap) — untapped programs
        # compile byte-identical
        qkv_mod, proj_mod, fc1, fc2 = self.blocks[i]
        bp = params
        k1 = k2 = k3 = None
        if key is not None:
            k1, k2, k3 = jax.random.split(key, 3)
        with jax.named_scope(f"block{i}"):
            with jax.named_scope("ln1"):
                h = _tap(self._ln(bp["ln1"], x), f"block{i}/ln1")
            # a residual add is fused into the GEMM before it, so it
            # carries that sublayer's scope: nothing of a block is
            # outside its four sublayers
            with jax.named_scope("attn"):
                attn = self._attention(bp, qkv_mod, proj_mod, h, k1)
                attn = _cn(attn, "attn_out")
                attn = _tap(attn, f"block{i}/attn")
                x = x + self._dropout(k2, attn)
            with jax.named_scope("ln2"):
                h = _tap(self._ln(bp["ln2"], x), f"block{i}/ln2")
            with jax.named_scope("mlp"):
                m = self._mlp(bp, fc1, fc2, h)
                m = _tap(m, f"block{i}/mlp")
                x = x + self._dropout(k3, m)
        return x

    def _mlp(self, bp, fc1, fc2, h):
        with jax.named_scope("fc1"):
            m = _cn(fc1.apply(bp["fc1"], h), "ffn1")
        with jax.named_scope("gelu"):
            m = jax.nn.gelu(m, approximate=True)
        with jax.named_scope("fc2"):
            return _cn(fc2.apply(bp["fc2"], m), "ffn_out")

    def apply(self, params, tokens, key=None):
        """tokens: (B, S) global int ids (replicated over tp).
        Returns hidden states (S[, /tp], B, H) local and a closure-free
        path to logits/loss below.  Shard-local: call inside shard_map.
        """
        c = self.c
        h = self._embed(params, tokens.T)  # (S,B,H) or (S/tp,B,H)
        if key is not None:
            key = model_parallel_fold_in(key, c.axis_name)
        for i in range(c.num_layers):
            bk = None if key is None else jax.random.fold_in(key, i)
            blk = lambda p, x: self._block(i, p, x, bk)
            if c.remat:
                if c.remat_policy == "dots":
                    pol = jax.checkpoint_policies.checkpoint_dots
                    blk = jax.checkpoint(blk, policy=pol)
                elif (isinstance(c.remat_policy, str)
                      and c.remat_policy.startswith("names:")):
                    names = tuple(
                        n for n in c.remat_policy[6:].split(",") if n)
                    bad = [n for n in names if n not in REMAT_TAGS]
                    if bad:
                        raise ValueError(
                            f"remat_policy names {bad} do not match any "
                            f"checkpoint_name tag in _block; known tags: "
                            f"{sorted(REMAT_TAGS)}")
                    pol = jax.checkpoint_policies.save_only_these_names(
                        *names)
                    blk = jax.checkpoint(blk, policy=pol)
                elif c.remat_policy is None:
                    blk = jax.checkpoint(blk)
                else:
                    raise ValueError(
                        f"unknown remat_policy {c.remat_policy!r}; "
                        "expected None, 'dots', or 'names:...'")
            h = blk(params[f"block{i}"], h)
        h = self._ln_final(params, h)
        return h

    def _embed(self, params, ids):
        """ids: (S, B).  Token plus position embedding."""
        c = self.c
        with jax.named_scope("embed"):
            h = self.embed.apply(params["embed"], ids)
            pos = params["pos_embed"][: ids.shape[0]][:, None, :]
            if c.sequence_parallel:
                pos = scatter_to_sequence_parallel_region(pos, c.axis_name)
            return h + pos.astype(h.dtype)

    def _ln_final(self, params, h):
        with jax.named_scope("final_ln"):
            return self._ln(params["final_ln"], h)

    def logits_local(self, params, h):
        """LM head with tied embedding weight → vocab-sharded logits
        (S, B, V/tp).  With SP the hidden is re-gathered first."""
        c = self.c
        w = params["embed"]["weight"]  # local (V/tp, H)
        # each rank's d(hidden) is partial (its vocab shard only) and
        # must be summed over tp exactly once on the way back: by the
        # gather's reduce-scatter under SP, by the copy's psum otherwise
        # (≡ ColumnParallelLinear; both together scale every gradient
        # upstream of the head by tp)
        with jax.named_scope("head"):
            if c.sequence_parallel:
                x = gather_from_sequence_parallel_region(h, c.axis_name)
            else:
                x = copy_to_tensor_model_parallel_region(h, c.axis_name)
            out_dtype = c.logits_dtype or jnp.float32
            return jnp.einsum("sbh,vh->sbv", x, w,
                              preferred_element_type=jnp.float32
                              ).astype(out_dtype)

    def loss(self, params, tokens, labels, key=None):
        """Mean LM loss.  tokens/labels: (B, S) global."""
        h = self.apply(params, tokens, key)
        logits = self.logits_local(params, h)  # (S,B,V/tp)
        return self._mean_loss(logits, labels.T)

    def _mean_loss(self, logits, labels):
        """logits (S, B, V/tp), labels (S, B) -> the mean token loss."""
        with jax.named_scope("loss"):
            return jnp.mean(vocab_parallel_cross_entropy(
                logits, labels, axis_name=self.c.axis_name,
                fused=self.c.fused_xent))


class GPTPipelined(GPT):
    """GPT over a (pp, dp, tp) mesh: blocks stacked per layer and
    sharded over pp; embedding / LM head replicated across stages (the
    reference places them on first/last stage with an embedding group
    allreduce, parallel_state.py:319-407 — here the tie is exact because
    every stage holds the same embed weight and grads mix via the
    pipeline's AD).  Microbatched via the SPMD clocked pipeline
    (pipeline_parallel/schedules.spmd_pipeline).
    """

    def __init__(self, config: GPTConfig, num_microbatches: int,
                 pipeline_parallel_size: int,
                 num_model_chunks: int = 1, remat_stage: bool = False,
                 checkpoint_window=None):
        super().__init__(config)
        c = config
        self.num_microbatches = num_microbatches
        self.pp = pipeline_parallel_size
        self.chunks = num_model_chunks
        self.remat_stage = remat_stage
        # 1F1B memory dial: jax.checkpoint window over pipeline clocks
        # (schedules.spmd_pipeline docstring); pp is the 1F1B-bound pick
        self.checkpoint_window = checkpoint_window
        assert c.num_layers % (self.pp * self.chunks) == 0, (
            "num_layers must divide pp * num_model_chunks")
        self.layers_per_stage = c.num_layers // (self.pp * self.chunks)

    def init(self, key):
        flat_params = super().init(key)
        c = self.c
        # stack per-layer block params: leaves (L, ...)
        blocks = [flat_params.pop(f"block{i}") for i in range(c.num_layers)]
        stacked = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *blocks)
        # reorder (L, ...) → (pp, chunks, layers_per_stage, ...):
        # global layer g = ((c_idx*pp + s) * lps + j)
        def reorder(l):
            return l.reshape(self.chunks, self.pp, self.layers_per_stage,
                             *l.shape[1:]).swapaxes(0, 1)
        flat_params["blocks"] = jax.tree_util.tree_map(reorder, stacked)
        return flat_params

    def partition_specs(self):
        base = super().partition_specs()
        c = self.c
        block_spec = base.pop("block0")
        for i in range(1, c.num_layers):
            base.pop(f"block{i}")
        # blocks leaves gained (pp, chunks, lps) leading dims; pp sharded
        def add_dims(spec):
            return P("pp", None, None, *spec)
        base["blocks"] = jax.tree_util.tree_map(
            add_dims, block_spec,
            is_leaf=lambda s: isinstance(s, P))
        return base

    def _stage_fn(self, stage_blocks, h, chunk):
        """Apply this stage's layers_per_stage blocks (scanned)."""
        def body(x, layer_params):
            return self._block_shared(layer_params, x, None), None
        h, _ = jax.lax.scan(body, h, stage_blocks)
        return h

    def _block_shared(self, bp, x, key):
        """_block with the (shared-config) layer modules of block 0."""
        qkv_mod, proj_mod, fc1, fc2 = self.blocks[0]
        # the scope is opened inside the scanned body, so that the
        # path reads while/body/block/ln1 in one piece
        with jax.named_scope("block"):
            with jax.named_scope("ln1"):
                h = self._ln(bp["ln1"], x)
            with jax.named_scope("attn"):
                x = x + self._attention(bp, qkv_mod, proj_mod, h, key)
            with jax.named_scope("ln2"):
                h = self._ln(bp["ln2"], x)
            with jax.named_scope("mlp"):
                return x + self._mlp(bp, fc1, fc2, h)

    def loss(self, params, tokens, labels, key=None):
        """tokens/labels: (B, S); B = num_microbatches × microbatch size.
        Shard-local (call inside shard_map over the full mesh)."""
        from apex_tpu.transformer.pipeline_parallel.schedules import (
            spmd_pipeline)
        m = self.num_microbatches
        B, S = tokens.shape
        assert B % m == 0
        mb = B // m
        ids = tokens.reshape(m, mb, S).transpose(0, 2, 1)  # (m, S, mb)

        h_mbs = jax.vmap(lambda ids_mb: self._embed(params, ids_mb))(
            ids)  # (m, S[, /tp], mb, H)

        # local stage params: drop the sharded pp dim (local size 1)
        stage_blocks = jax.tree_util.tree_map(lambda l: l[0],
                                              params["blocks"])

        def stage_fn(chunk_blocks, x, chunk):
            return self._stage_fn(chunk_blocks, x, chunk)

        def head_one(h_mb, labels_mb):
            h_f = self._ln_final(params, h_mb)
            logits = self.logits_local(params, h_f)  # (S, mb, V/tp)
            return self._mean_loss(logits, labels_mb)

        lbl = labels.reshape(m, mb, S).transpose(0, 2, 1)  # (m, S, mb)
        # head + loss run on the LAST STAGE inside the clocked scan and
        # only a scalar crosses the pp axis (the old path psum'd the
        # whole (m, S, mb, H) stacked output every step)
        total = spmd_pipeline(stage_fn, stage_blocks, h_mbs,
                              num_model_chunks=self.chunks,
                              remat_stage=self.remat_stage,
                              checkpoint_window=self.checkpoint_window,
                              loss_fn=head_one, loss_args=lbl)
        return total / m


def qkv_as_tp1(tree, config: GPTConfig, tp: int):
    """The tp=1 spelling of a GPT param (or grad) pytree laid out for
    tensor parallelism `tp`.

    Each rank views ITS columns of the packed QKV projection as
    (3, heads/tp, head_dim), so the global column order is rank-major,
    (tp, 3, heads/tp, d), where tp=1 reads (3, heads, d): the same
    global arrays are a different network under a different tp.  Every
    other leaf means the same at any tp.  Regrouped by this function, a
    tp=`tp` tree computes under tp=1 exactly what it computed under tp."""
    def regroup(x):
        lead = x.shape[:-1]
        x = x.reshape(*lead, tp, 3, config.num_heads // tp, config.head_dim)
        return jnp.moveaxis(x, -4, -3).reshape(*lead, -1)

    out = dict(tree)
    for i in range(config.num_layers):
        block = dict(tree[f"block{i}"])
        block["qkv"] = jax.tree_util.tree_map(regroup, block["qkv"])
        out[f"block{i}"] = block
    return out


def gpt_350m(**overrides) -> GPT:
    cfg = {**GPT2_350M, **overrides}
    return GPT(GPTConfig(**cfg))


def gpt_1p3b(**overrides) -> GPT:
    cfg = {**GPT2_1p3B, **overrides}
    return GPT(GPTConfig(**cfg))
