"""chip_smoke.py — does the system still start on the chip?

A smoke, not a benchmark: it drives the main path once on one TPU chip
through the entry points a user calls, at the full width of GPT-350M,
and checks what comes out by the repo's own means.

    python chip_smoke.py               # one chip: kernels, train, serve
    python chip_smoke.py --multichip   # four chips: tp=2 x dp=2 vs tp=1

One process, no child, no CPU fallback.  Every line on stdout is one
JSON object; the last is `{"ok": true, "device": {...}}`, or carries
`"ok": false` when JAX finds no TPU or any phase raised (the exception
then propagates and the exit code is non-zero).  The seconds printed
are host wall time around `jax.block_until_ready` and say whether the
program ran, not how fast the system is.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
from typing import Callable, Optional, Sequence

# GPT-350M as bench.py trains it: the widths are the model's, nothing cut
FLAGSHIP = dict(vocab_size=50304, seq_len=1024, hidden=1024, num_layers=24,
                num_heads=16)
BATCH, SEQ = 12, 1024
LN_VOCAB_TOL = 0.5      # tests/test_gpt_minimal.py::test_init_loss_near_uniform
# Full-size Adam steps from step 1, with no warm-up, overshoot on one fixed
# batch: at lr 1e-4 the 350M loss wobbles (11.02, 10.81, 10.91, 10.66,
# 10.40, 11.07 on the chip, kernels and jnp references alike), at 1e-5 it
# falls on every step.  A smoke wants the second.
LR = 1e-5
MULTICHIP_RTOL = 2e-3   # per-step loss, tp=2 x dp=2 (bf16) against tp=1


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def compile_account(earlier: dict) -> dict:
    """What the set-up ledger (`apex_tpu.monitor.compile.startup`) has
    counted in this process: the persistent cache's hits and misses so
    far, and the seconds of tracing, lowering, compiling and reading
    the cache since `earlier` (the totals of the call before, updated
    in place)."""
    from apex_tpu.monitor.compile import startup

    phases = startup.ledger()["totals"].values()
    now = {k: sum(t[k] for t in phases) for k in (
        "programs", "cache_hits", "cache_misses", "trace_s", "lower_s",
        "compile_s", "cache_read_s")}
    since = {k: round(v - earlier.get(k, 0), 3) for k, v in now.items()
             if k.endswith("_s") or k == "programs"}
    earlier.update(now)
    return {"persistent_cache_hits": now["cache_hits"],
            "persistent_cache_misses": now["cache_misses"],
            "compile_account": since}


# ------------------------------- kernels -------------------------------

@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One main-path kernel at one real-width shape: the dispatching op
    as callers reach it, its own jnp reference, and the tolerance
    `|got - want| <= atol + rtol * |want|` held elementwise."""

    name: str
    make_args: Callable        # PRNG key -> tuple of arrays
    kernel: Callable           # *args -> tuple of arrays
    reference: Callable        # *args -> tuple of arrays
    rtol: float
    atol: float
    mosaic: bool = True        # a tpu_custom_call must be in the program
    # errors and `want` counted in units of each reference output's root
    # mean square: for outputs of unlike sizes (an activation, gradients)
    in_rms: bool = False


def _attention_fwd_bwd(attn, q, k, v, do):
    """(out, dq, dk, dv) of `attn(q, k, v)` under the cotangent `do`."""
    import jax

    out, vjp = jax.vjp(attn, q, k, v)
    return (out,) + vjp(do)


def _seeded_documents(key, batch, seq, mean=845):
    """(ids (B, S) int32, first (B, S) bool): documents of a packed
    row, a boundary after a token with probability 1 / mean."""
    import jax
    import jax.numpy as jnp

    after = jax.random.bernoulli(key, 1.0 / mean, (batch, seq))
    first = jnp.pad(after[:, :-1], ((0, 0), (1, 0)), constant_values=True)
    return jnp.cumsum(first, axis=1, dtype=jnp.int32), first


def flash_case(name, shape, config: Optional[dict] = None,
               v_dim: Optional[int] = None,
               documents: bool = False) -> KernelCase:
    """Causal bf16 flash attention, forward and backward, against the
    dense reference.  config None leaves the kernel shape to the tuner
    and the heuristics, as the models do.  `v_dim` gives v (and the
    output) a width of its own, as latent attention has it; with
    `documents` a row is packed documents and the call carries their
    segment ids.  The reference walks the batch (and, a row of
    documents being long, the heads) one at a time so its (S, S)
    scores stay small."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.flash_attention import (
        attention_reference,
        flash_attention,
    )

    v_shape = tuple(shape[:-1]) + (v_dim or shape[-1],)

    def make_args(key):
        ks = jax.random.split(key, 5)      # q, k at `shape`; v, do at v's
        args = tuple(jax.random.normal(k, s, jnp.bfloat16) for k, s in zip(
            ks, (shape, shape, v_shape, v_shape)))
        if documents:
            args += (_seeded_documents(ks[4], shape[0], shape[2])[0],)
        return args

    fwd_bwd = _attention_fwd_bwd

    def kernel(q, k, v, do, ids=None):
        return fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, segment_ids=ids, **(config or {})),
            q, k, v, do)

    def reference(q, k, v, do, ids=None):
        if ids is None:
            def one(row):
                return fwd_bwd(lambda q, k, v: attention_reference(
                    q, k, v, causal=True), *(a[None] for a in row))
            outs = jax.lax.map(one, (q, k, v, do))
            return tuple(o[:, 0] for o in outs)

        def head(x):                       # a head of a row at a time
            *row, ids = x
            return fwd_bwd(lambda q, k, v: attention_reference(
                q, k, v, causal=True, q_segment_ids=ids[None],
                kv_segment_ids=ids[None]), *(a[None, None] for a in row))
        b, h = shape[:2]
        flat = [a.reshape(b * h, *a.shape[2:]) for a in (q, k, v, do)]
        outs = jax.lax.map(head, (*flat, jnp.repeat(ids, h, axis=0)))
        return tuple(o.reshape(b, h, *o.shape[3:]) for o in outs)

    # bf16 in and out: a few bf16 ulps at the O(1) magnitudes attention
    # produces, the bound tests/test_flash_attention.py holds bf16 to
    return KernelCase(name, make_args, kernel, reference, 5e-2, 5e-2)


def gqa_flash_case(name, batch, heads, kv_heads, seq, head_dim,
                   config: Optional[dict] = None) -> KernelCase:
    """Causal bf16 grouped-query flash attention (`kv_heads` serving
    `heads` query heads, no bias, no rotary: models/hybrid_moe.py),
    forward and dq, dk, dv, against the dense reference on k and v
    repeated a query head.  The reference walks the kv heads one at a
    time so its (group, S, S) scores stay small, and its dk, dv are
    the sums over a group that the repeat's transpose makes.  config
    None leaves the kernel shape to the tuner, as the model does."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.flash_attention import (
        attention_reference,
        flash_attention,
    )

    group = heads // kv_heads

    def make_args(key):
        ks = jax.random.split(key, 4)      # q, k, v, do
        return tuple(jax.random.normal(k, (batch, h, seq, head_dim),
                                       jnp.bfloat16)
                     for k, h in zip(ks, (heads, kv_heads, kv_heads, heads)))

    fwd_bwd = _attention_fwd_bwd

    def kernel(q, k, v, do):
        return fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, **(config or {})), q, k, v, do)

    def reference(q, k, v, do):
        def one(x):       # a kv head and its query heads: (B, group|1, S, d)
            return fwd_bwd(lambda q, k, v: attention_reference(
                q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1),
                causal=True), *x)

        def by_kv_head(x):
            b, h, s, d = x.shape
            return x.reshape(b, kv_heads, h // kv_heads, s, d).swapaxes(0, 1)

        outs = jax.lax.map(one, tuple(map(by_kv_head, (q, k, v, do))))
        return tuple(o.swapaxes(0, 1).reshape(a.shape)
                     for o, a in zip(outs, (q, q, k, v)))

    # dk and dv sum eight query heads' parts, so they are larger than a
    # query head's and are held to the same relative bound
    return KernelCase(name, make_args, kernel, reference, 5e-2, 5e-2)


def delta_rule_case(name, batch, heads, seq, dim, heads_a_pass=4,
                    documents: bool = False) -> KernelCase:
    """The chunked gated delta rule (ops/delta_rule.py) in bf16 at the
    benchmark's shape, forward and all five gradients, against the
    recurrence a token at a time in float32.  Inputs as KDA makes them:
    q and k of unit length (q by d^-1/2 more), the log-decay
    -A softplus(.) with A in (1, 16) and a time step in (1e-3, 1e-1),
    beta in (0, 2), or in (0, 1) with `documents`: then a row is packed
    documents, the op is handed their first tokens as `resets` and the
    recurrence sets its state to 0 there, exactly.  The reference walks
    the heads a few at a time: its backward keeps a state a token."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.delta_rule import (
        gated_delta_rule,
        gated_delta_rule_reference,
    )

    shape = (batch, heads, seq, dim)

    def make_args(key):
        ks = jax.random.split(key, 8)

        def unit(k, scale=1.0):
            x = jax.random.normal(k, shape, jnp.float32)
            return (x * scale / jnp.linalg.norm(x, axis=-1, keepdims=True)
                    ).astype(jnp.bfloat16)
        rate = jax.random.uniform(ks[3], (1, heads, 1, 1), jnp.float32, 1, 16)
        dt = jnp.exp(jax.random.uniform(
            ks[4], (1, heads, 1, dim), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        g = -rate * jax.nn.softplus(
            jnp.log(jnp.expm1(dt)) + 0.3 * jax.random.normal(ks[5], shape))
        beta = jax.nn.sigmoid(jax.random.normal(ks[6], shape[:3]))
        args = (unit(ks[0], dim ** -0.5), unit(ks[1]),
                jax.random.normal(ks[2], shape, jnp.bfloat16), g,
                beta if documents else 2 * beta,
                jax.random.normal(ks[7], shape, jnp.bfloat16))
        if documents:
            args += (_seeded_documents(jax.random.fold_in(ks[5], 1), batch,
                                       seq)[1],)
        return args

    def fwd_bwd(rule, q, k, v, g, beta, do):
        out, vjp = jax.vjp(rule, q, k, v, g, beta)
        return (out,) + vjp(do.astype(out.dtype))

    def kernel(*args):
        first = args[6] if documents else None
        return fwd_bwd(lambda *a: gated_delta_rule(*a, resets=first),
                       *args[:6])

    def reference(*args):
        first = args[6] if documents else None
        args = args[:6]

        def some(x):
            return fwd_bwd(lambda *a: gated_delta_rule_reference(
                *a, resets=first), *x)

        def by_pass(x):    # (B, n, ...) -> (n / pass, B, pass, ...)
            return x.reshape(batch, heads // heads_a_pass, heads_a_pass,
                             *x.shape[2:]).swapaxes(0, 1)

        outs = jax.lax.map(some, tuple(map(by_pass, args)))
        return tuple(o.swapaxes(0, 1).reshape(a.shape)
                     for o, a in zip(outs, args[2:3] + args[:5]))

    # in units of each output's rms: bf16 operands in the chunk-local
    # products against float32 throughout
    return KernelCase(name, make_args, kernel, reference, 5e-2, 0.2,
                      in_rms=True)


def conv_stage_case(name, shapes) -> KernelCase:
    """KDA's staging of q, k, v for the scan (ops/conv_stage.py) in
    bf16, the Pallas pair against the `jax.numpy` body in float32:
    the three head-major outputs and the gradients of the streams and
    of the taps, at each of `shapes` = (batch, heads, seq, dim,
    documents), the two hybrid cells'; with `documents` a row is packed
    documents and the taps stop at their first tokens."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.conv_stage import (
        stage_conv_heads,
        stage_conv_heads_reference,
    )

    def make_args(key):
        args = []
        for i, (batch, heads, seq, dim, documents) in enumerate(shapes):
            ks = jax.random.split(jax.random.fold_in(key, i), 10)
            wide = heads * dim
            xs = tuple(jax.random.normal(k, (batch, seq, wide), jnp.bfloat16)
                       for k in ks[:3])
            ws = tuple((jax.random.uniform(k, (4, wide), minval=-1.0) / 2
                        ).astype(jnp.bfloat16) for k in ks[3:6])
            cots = tuple(jax.random.normal(
                k, (batch, heads, seq, dim), jnp.bfloat16) for k in ks[6:9])
            ids = _seeded_documents(ks[9], batch, seq)[0] if documents \
                else None
            args.append((xs, ws, cots, ids))
        return tuple(args)

    def fwd_bwd(stage, dtype, xs, ws, cots, ids, heads, dim):
        cast = lambda t: tuple(x.astype(dtype) for x in t)
        outs, pull = jax.vjp(
            lambda xs, ws: stage(xs, ws, heads, (dim ** -0.5, 1.0, None),
                                 ids=ids), cast(xs), cast(ws))
        dxs, dws = pull(cast(cots))
        return (*outs, *dxs, *dws)

    def run(stage, dtype, *args):
        return tuple(x for arg, (_, heads, _, dim, _) in zip(args, shapes)
                     for x in fwd_bwd(stage, dtype, *arg, heads, dim))

    # in units of each output's rms, the band of `delta_rule_grads`:
    # bf16's rounding of the taps' sum and of the results against
    # float32 throughout
    return KernelCase(
        name, make_args, lambda *a: run(stage_conv_heads, jnp.bfloat16, *a),
        lambda *a: run(stage_conv_heads_reference, jnp.float32, *a),
        5e-2, 0.2, in_rms=True)


def short_conv_case(name, batch, seq, hidden, taps=3) -> KernelCase:
    """The gated short convolution of a convolutional mixer
    (ops/short_conv.py) in bf16, as the step compiles it, against the
    same body in float32: the output and the gradients of the
    projection's three thirds and of the taps."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.short_conv import gated_short_conv

    def make_args(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return (jax.random.normal(k1, (batch, seq, 3 * hidden), jnp.bfloat16),
                (jax.random.uniform(k2, (hidden, taps), minval=-1.0)
                 / math.sqrt(taps)).astype(jnp.bfloat16),
                jax.random.normal(k3, (batch, seq, hidden), jnp.bfloat16))

    def run(dtype, bcu, w, dy):
        out, pull = jax.vjp(gated_short_conv, bcu.astype(dtype),
                            w.astype(dtype))
        return (out, *pull(dy.astype(dtype)))

    # in units of each output's rms: one bf16 rounding of the output
    # and of each gradient against float32 throughout (the taps'
    # gradient sums 8,192 tokens in float32 on both sides)
    return KernelCase(
        name, make_args, lambda *a: run(jnp.bfloat16, *a),
        lambda *a: run(jnp.float32, *a), 2e-2, 5e-2, mosaic=False,
        in_rms=True)


def flash_qkv_case(name, seq, batch, heads, head_dim) -> KernelCase:
    """Causal bf16 flash attention from the packed (S, B, 3*H)
    projection to the (S, B, H) context, forward and backward, as
    GPT._attention calls it, against the dense reference on the split
    heads."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.flash_attention import (
        attention_reference,
        flash_attention_qkv,
    )
    from apex_tpu.ops.fused_dense import qkv_split_heads

    hidden = heads * head_dim

    def make_args(key):
        k1, k2 = jax.random.split(key)
        return (jax.random.normal(k1, (seq, batch, 3 * hidden), jnp.bfloat16),
                jax.random.normal(k2, (seq, batch, hidden), jnp.bfloat16))

    def fwd_bwd(attn, qkv, dctx):
        out, vjp = jax.vjp(attn, qkv)
        return (out,) + vjp(dctx)

    def kernel(qkv, dctx):
        return fwd_bwd(lambda x: flash_attention_qkv(x, heads, causal=True),
                       qkv, dctx)

    def reference(qkv, dctx):
        def dense(x):
            def one(row):       # (3, heads, S, d): one batch row
                return attention_reference(*(a[None] for a in row),
                                           causal=True)[0]
            ctx = jax.lax.map(one, jnp.stack(
                qkv_split_heads(x, heads, head_dim), axis=1))
            return ctx.transpose(2, 0, 1, 3).reshape(seq, batch, hidden)
        return fwd_bwd(dense, qkv, dctx)

    return KernelCase(name, make_args, kernel, reference, 5e-2, 5e-2)


def held_experts_case(name, tokens, hidden, ffn, n_experts, count,
                      top_k, **layer_options) -> KernelCase:
    """`moe.HeldExpertsMLP` (sigmoid router over `n_experts`, the
    sort-by-expert grouping, grouped GEMMs over the `count` experts
    held, the shared expert) in bf16, forward and the gradients of the
    input and of the experts' tensors, against the same sum written
    densely: every held expert over every token, times the weight the
    same router gave it (0 where it was not chosen).  `layer_options`
    go to the layer: the buffer's size."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.moe import HeldExpertsMLP, sigmoid_topk_gates
    from apex_tpu.moe.layer import swiglu

    layer = HeldExpertsMLP(hidden, ffn, n_experts, first=0, count=count,
                           top_k=top_k, scale=2.5, bias_range=0.05,
                           **layer_options)
    trained = ("experts_gate_up", "experts_down")
    # a weight gradient is a sum over an expert's rows, tokens * top_k /
    # n_experts of them on average: divided by the root of that, it has
    # the size of a row's share, like the other outputs
    per_row = 1.0 / math.sqrt(tokens * top_k / n_experts)

    def make_args(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return (layer.init(k1, jnp.bfloat16),
                jax.random.normal(k2, (tokens, hidden), jnp.bfloat16),
                jax.random.normal(k3, (tokens, hidden), jnp.bfloat16))

    def fwd_bwd(apply, params, x, dy):
        rest = {k: v for k, v in params.items() if k not in trained}
        y, vjp = jax.vjp(
            lambda x, w: apply({**rest, **w}, x), x,
            {k: params[k] for k in trained})
        dx, dw = vjp(dy)
        return (y, dx) + tuple(dw[k].astype(jnp.float32) * per_row
                               for k in trained)

    def kernel(params, x, dy):
        return fwd_bwd(lambda p, x: layer.apply(p, x)[0], params, x, dy)

    def dense(params, x):
        gates = sigmoid_topk_gates(x, params["router"],
                                   params["router_bias"], top_k, scale=2.5)

        def one(y, e):      # expert e over every token
            w = jnp.sum(jnp.where(gates.idx == e, gates.weight, 0.0), -1)
            out = swiglu(x, params["experts_gate_up"][e],
                         params["experts_down"][e])
            return y + w[:, None] * out.astype(jnp.float32), None

        y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                            jnp.arange(count))
        if layer.n_shared:
            y = y + swiglu(x, params["shared_gate_up"],
                           params["shared_down"])
        return y.astype(x.dtype)

    def reference(params, x, dy):
        return fwd_bwd(dense, params, x, dy)

    # bf16 in and out; on the chip (PR 28) the outputs' rms is 0.27,
    # 0.39, 0.14, 0.14 and the worst errors 0.004, 0.010, 0.006, 0.004.
    # Before the layer zeroed the rows past its last group the input
    # gradient was off by 3.7: a grouped GEMM on the chip leaves them
    # as it finds them, which no CPU run shows
    return KernelCase(name, make_args, kernel, reference, 5e-2, 2.5e-2)


def mla_attention_case(name, batch, seq) -> KernelCase:
    """`MLAMoE._attention` at JoyAI-LLM-Flash's widths in bf16 (the
    staging pass `rope_stage` in front of the flash kernels at keys 192
    / values 128), forward and the gradients of `q_b`, `kv_a`, `kv_b`
    and `proj`, against the published-order formulation in float32 on
    the same weights: interleaved pairs turned by a roll, k's rotary
    row broadcast to the heads, one GEMM a projection, dense attention
    a batch row at a time.  The benchmark's `correct` compares losses
    and cannot see a wrong backward; this can."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.mla_moe import MLAMoE, MLAMoEConfig
    from apex_tpu.ops.flash_attention import attention_reference

    model = MLAMoE(MLAMoEConfig(dtype=jnp.bfloat16))
    c = model.c
    nh, dn, dr, dv = (c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                      c.v_head_dim)
    trained = ("q_b", "kv_a", "kv_b", "proj")

    def make_args(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return (model._init_block(k1, 0)["attn"],
                jax.random.normal(k2, (batch, seq, c.hidden), jnp.bfloat16),
                jax.random.normal(k3, (batch, seq, c.hidden), jnp.bfloat16))

    def fwd_bwd(attention, p, a, dy):
        rest = {k: v for k, v in p.items() if k not in trained}
        y, vjp = jax.vjp(lambda w: attention({**rest, **w}, a),
                         {k: p[k] for k in trained})
        (dw,) = vjp(dy.astype(y.dtype))
        return (y,) + tuple(dw[k] for k in trained)

    def kernel(p, a, dy):
        return fwd_bwd(lambda p, a: model._attention(
            p, a, model._tables(0, seq)), p, a, dy)

    def turn_pairs(x):
        """(..., S, n, dr), pairs (2i, 2i+1) turned by position."""
        inv_freq = c.rope_theta ** (-jnp.arange(0, dr, 2) / dr)
        angle = jnp.arange(seq)[:, None] * inv_freq
        cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)[:, None]
        sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)[:, None]
        partner = jnp.where(jnp.arange(dr) % 2 == 0,
                            -jnp.roll(x, -1, axis=-1),
                            jnp.roll(x, 1, axis=-1))
        return x * cos + partner * sin

    def published(p, a):
        def norm(x, w):
            return x * jax.lax.rsqrt(jnp.mean(
                x * x, -1, keepdims=True) + c.rms_norm_eps) * w["weight"]
        q = (norm(a @ p["q_a"], p["q_a_norm"]) @ p["q_b"]).reshape(
            batch, seq, nh, dn + dr)
        ckv = a @ p["kv_a"]
        kv = (norm(ckv[..., :c.kv_lora_rank], p["kv_a_norm"])
              @ p["kv_b"]).reshape(batch, seq, nh, dn + dv)
        k_r = turn_pairs(ckv[:, :, None, c.kv_lora_rank:])
        q = jnp.concatenate([q[..., :dn], turn_pairs(q[..., dn:])], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (batch, seq, nh, dr))], -1)

        def one(row):       # (nh, S, d) each: one batch row
            return attention_reference(
                *(x[None] for x in row), causal=True,
                softmax_scale=1.0 / math.sqrt(dn + dr))[0]
        ctx = jax.lax.map(one, tuple(
            x.transpose(0, 2, 1, 3) for x in (q, k, kv[..., dn:])))
        return ctx.transpose(0, 2, 1, 3).reshape(batch, seq, nh * dv) \
            @ p["proj"]

    def reference(p, a, dy):
        f32 = jax.tree.map(lambda x: x.astype(jnp.float32), (p, a, dy))
        with jax.default_matmul_precision("highest"):
            return fwd_bwd(published, *f32)

    # bf16 against float32, each output in units of its own root mean
    # square (the output's is 0.03, a weight gradient's a few hundred):
    # the band is set from the chip's readings, given beside the case in
    # `kernel_cases`; a rotary turned the wrong way, or a head left out
    # of `kv_a`'s sum, is an error of about 1
    return KernelCase(name, make_args, kernel, reference, 5e-2, 0.2,
                      in_rms=True)


def adam_case(name, n, state_dtype) -> KernelCase:
    """adam_flat over n elements with bf16 grads against its jnp
    reference (the same op with the kernel switched off)."""
    import functools

    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import optimizer_kernels as K

    n = -(-n // K.FLAT_TILE) * K.FLAT_TILE

    def make_args(key):
        kp, km, kv, kg = jax.random.split(key, 4)
        return ((jax.random.normal(kp, (n,), jnp.float32) * 0.02
                 ).astype(state_dtype),
                (jax.random.normal(km, (n,), jnp.float32) * 1e-3
                 ).astype(state_dtype),
                (jax.random.uniform(kv, (n,), jnp.float32) * 1e-6
                 ).astype(state_dtype),
                (jax.random.normal(kg, (n,), jnp.float32) * 1e-3
                 ).astype(jnp.bfloat16))

    step = functools.partial(K.adam_flat, lr=1e-4, step=10,
                             weight_decay=0.01)
    # both sides do the update in fp32 and round once to the state
    # dtype: a few ulps of that dtype, plus what cancellation between
    # 1e-3-sized moments and grads leaves in fp32
    rtol = 4 * float(jnp.finfo(state_dtype).eps)
    return KernelCase(
        name, make_args, step,
        functools.partial(step, use_pallas_override=False), rtol, 1e-9)


def _loss_and_dlogits(loss_fn, logits, labels, g):
    import jax

    loss, vjp = jax.vjp(lambda x: loss_fn(x, labels), logits)
    return loss, vjp(g)[0]


def xent_case(name, rows, vocab) -> KernelCase:
    """The Pallas softmax cross entropy on bf16 logits, loss and
    d(logits), against its jnp reference."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.xentropy import (
        softmax_cross_entropy_loss,
        softmax_cross_entropy_reference,
    )

    def make_args(key):
        kl, kt, kg = jax.random.split(key, 3)
        return (jax.random.normal(kl, (rows, vocab), jnp.bfloat16),
                jax.random.randint(kt, (rows,), 0, vocab),
                jax.random.uniform(kg, (rows,), jnp.float32))

    # fp32 math on both sides; d(logits) is rounded to bf16 once
    return KernelCase(
        name, make_args,
        lambda *a: _loss_and_dlogits(softmax_cross_entropy_loss, *a),
        lambda *a: _loss_and_dlogits(softmax_cross_entropy_reference, *a),
        2e-2, 1e-6)


def vocab_parallel_xent_case(name, seq, batch, vocab, device) -> KernelCase:
    """The loss the GPT step really calls: the fused custom_vjp
    vocab-parallel cross entropy on bf16 logits against the unfused AD
    spelling.  Plain XLA, no Mosaic kernel; it runs under a tp axis of
    `device` alone, as it does inside the one-chip step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.transformer.tensor_parallel.cross_entropy import (
        vocab_parallel_cross_entropy,
    )

    def make_args(key):
        kl, kt, kg = jax.random.split(key, 3)
        return (jax.random.normal(kl, (seq, batch, vocab), jnp.bfloat16),
                jax.random.randint(kt, (seq, batch), 0, vocab),
                jax.random.uniform(kg, (seq, batch), jnp.float32))

    mesh = Mesh(np.asarray([device]), ("tp",))

    def run(fused, *args):
        def loss_fn(logits, labels):
            return vocab_parallel_cross_entropy(logits, labels,
                                                axis_name="tp", fused=fused)

        return shard_map(
            lambda *a: _loss_and_dlogits(loss_fn, *a), mesh=mesh,
            in_specs=(P(), P(), P()), out_specs=(P(), P()),
            check_vma=False)(*args)

    return KernelCase(
        name, make_args, lambda *a: run(True, *a),
        lambda *a: run(False, *a), 2e-2, 1e-6, mosaic=False)


def decode_case(name, n_slots, heads, head_dim, page, pages_per_slot
                ) -> KernelCase:
    """flash_decode at a serving engine's shape: one query token per
    slot against a paged bf16 cache, slots at every fill level
    (empty included), against the dense gathered reference."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.flash_decode import (
        flash_decode,
        paged_attention_reference,
    )

    n_pages = 1 + n_slots * pages_per_slot

    def make_args(key):
        kq, kk, kv, kl = jax.random.split(key, 4)
        pool = (heads, n_pages, page, head_dim)
        table = 1 + jnp.arange(n_slots * pages_per_slot, dtype=jnp.int32
                               ).reshape(n_slots, pages_per_slot)
        return (jax.random.normal(kq, (n_slots, 1, heads, head_dim),
                                  jnp.bfloat16),
                jax.random.normal(kk, pool, jnp.bfloat16),
                jax.random.normal(kv, pool, jnp.bfloat16),
                table,
                jax.random.randint(kl, (n_slots,), 0,
                                   page * pages_per_slot + 1))

    return KernelCase(
        name, make_args,
        lambda *a: (flash_decode(*a),),
        lambda *a: (paged_attention_reference(*a),), 5e-2, 5e-2)


def flagship_config(**overrides):
    """GPT-350M as bench.py trains it."""
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTConfig

    return GPTConfig(dropout=0.0, dtype=jnp.bfloat16,
                     logits_dtype=jnp.bfloat16, remat=False,
                     use_flash_attention=True, **FLAGSHIP, **overrides)


def kernel_cases(device) -> list:
    """Every kernel of the GPT-350M train and serve path, at the shape
    that path gives it, for the chip `device`."""
    import jax.numpy as jnp

    h = FLAGSHIP["num_heads"]
    d = FLAGSHIP["hidden"] // h
    n_params = 354_000_000
    return [
        flash_case("flash_350m", (BATCH, h, SEQ, d)),
        flash_qkv_case("flash_qkv_350m", SEQ, BATCH, h, d),
        # head-major at 64 wide with several blocks on the causal grid
        flash_case("flash_d64_s2048", (8, 16, 2048, 64)),
        # latent attention: keys 192 wide, values 128 (models/mla_moe.py)
        flash_case("flash_mla_192_128", (2, 32, 4096, 192), v_dim=128),
        # the whole latent attention around them, gradients and all: past
        # rtol the output reads 0.11-0.14 of its rms and the gradients of
        # q_b, kv_a, kv_b, proj 0.03, 0.02, 0.03, 0.02 (three seeds, PR 31)
        mla_attention_case("mla_attention_grads", 2, 4096),
        # grouped-query attention, 64 query heads on 8 kv heads, and the
        # chunked gated delta rule, both at the hybrid cell's shape
        # (models/hybrid_moe.py): `correct` cannot see a wrong backward
        gqa_flash_case("gqa_flash_grads", 1, 64, 8, 4096, 128),
        delta_rule_case("delta_rule_grads", 1, 64, 4096, 128),
        # a row of 8,192 tokens of packed documents at the Kimi-Linear
        # cell's shapes: latent attention's two widths under the segment
        # mask, and the delta rule with its state reset at every
        # document's first token, against the exact reset
        flash_case("flash_nope_192_128_docs", (1, 32, 8192, 192), v_dim=128,
                   documents=True),
        delta_rule_case("delta_rule_docs_grads", 1, 32, 8192, 128,
                        documents=True),
        # the way from KDA's projections to those two calls: taps, SiLU,
        # unit scaling and the head-major order in one pass a direction
        conv_stage_case("conv_stage_grads", [(1, 64, 4096, 128, False),
                                             (1, 32, 8192, 128, True)]),
        # one chip's 16 of 256 experts over 8,192 tokens, 8 a token
        held_experts_case("moe_held_experts", 8192, 2048, 768, 256, 16, 8),
        # one chip's 8 of 320 experts of 1280 over 4,096 tokens, a
        # buffer of eight times the expectation (the hybrid cell's
        # expert layer)
        held_experts_case("moe_held_experts_320", 4096, 4096, 1280, 320, 8, 8,
                          rows_factor=8.0),
        # the short-convolution stack's (models/shortconv_moe.py): the
        # gates and taps of one mixer over a row of 8,192; its one
        # attention call, 32 query heads on 8 kv heads of 64; and one
        # chip's 16 of 32 experts of 1792 with nothing shared, 4 a
        # token, 1,024 rows an expert, the buffer every assignment
        short_conv_case("short_conv_grads", 1, 8192, 2048),
        gqa_flash_case("gqa_flash_d64_grads", 1, 32, 8, 8192, 64),
        held_experts_case("moe_held_experts_half", 8192, 2048, 1792, 32, 16,
                          4, n_shared=0),
        adam_case("adam_flat_fp32", n_params, jnp.float32),
        adam_case("adam_flat_bf16", n_params, jnp.bfloat16),
        xent_case("xent_pallas", BATCH * SEQ, FLAGSHIP["vocab_size"]),
        vocab_parallel_xent_case("xent_vocab_parallel", SEQ, BATCH,
                                 FLAGSHIP["vocab_size"], device),
        # build_flagship_engine(True): 64 slots, 128 + 128 tokens a
        # slot in 128-token pages
        decode_case("flash_decode", 64, h, d, 128, 2),
    ]


def kernel_check(case: KernelCase):
    """jit(args -> per-output [worst tolerance excess, worst abs error,
    all finite]): kernel, reference and comparison in one program, so
    only scalars outlive it on the device."""
    import jax
    import jax.numpy as jnp

    def check(*args):
        rows = []
        for got, want in zip(case.kernel(*args), case.reference(*args),
                             strict=True):
            got, want = got.astype(jnp.float32), want.astype(jnp.float32)
            if case.in_rms:
                unit = jnp.sqrt(jnp.mean(want * want))
                got, want = got / unit, want / unit
            err = jnp.abs(got - want)
            rows.append(jnp.stack([
                jnp.max(err - case.rtol * jnp.abs(want)), jnp.max(err),
                jnp.all(jnp.isfinite(got)).astype(jnp.float32)]))
        return jnp.stack(rows)

    return jax.jit(check)


def run_kernel_case(case: KernelCase, seed: int, require_chip: bool) -> dict:
    """Compile, execute and compare one case; raises on a mismatch, a
    non-finite value, or (on the chip) a program without its kernel."""
    import jax
    import numpy as np

    args = case.make_args(jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    compiled = kernel_check(case).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    n_calls = compiled.as_text().count("tpu_custom_call")
    if require_chip and case.mosaic and n_calls == 0:
        raise RuntimeError(
            f"{case.name}: no tpu_custom_call in the compiled program — "
            "the op took its jnp reference instead of the kernel")
    stats = np.asarray(jax.block_until_ready(compiled(*args)))
    excess, max_err, finite = stats[:, 0], stats[:, 1], stats[:, 2]
    if not (finite.all() and (excess <= case.atol).all()):
        raise RuntimeError(
            f"{case.name}: kernel and reference disagree beyond rtol="
            f"{case.rtol} atol={case.atol}: excess={excess.tolist()} "
            f"max_abs_err={max_err.tolist()} finite={finite.tolist()}")
    return {"kernel": case.name, "compile_s": round(compile_s, 2),
            "tpu_custom_calls": n_calls, "rtol": case.rtol,
            "atol": case.atol, "max_abs_err": max_err.tolist()}


def phase_kernels(device, seed: int) -> None:
    from apex_tpu.ops._common import pallas_interpret

    if pallas_interpret():
        raise RuntimeError("pallas_interpret() is true on the chip: the "
                           "kernels would run interpreted, not compiled")
    for case in kernel_cases(device):
        emit(phase="kernels", **run_kernel_case(case, seed,
                                                require_chip=True))
        gc.collect()


# ----------------------------- train steps -----------------------------

def _collectives(text: str) -> dict:
    kinds = ("all-gather", "all-reduce", "reduce-scatter",
             "collective-permute", "all-to-all")
    return {k: text.count(f" {k}(") + text.count(f" {k}-start(")
            for k in kinds}


def train_run(cfg, devices: Sequence, tp: int, batch: int, steps: int,
              seed: int, require_chip: bool, weights_of_tp: int = 1) -> dict:
    """The README quick-start path on `devices`: mesh -> GPT ->
    FusedAdam(bf16 state) -> init_sharded_optimizer ->
    make_tp_dp_train_step(donate=True), then `steps` steps on one fixed
    seeded batch under the RecompileSentry (the first two are warm-up).
    weights_of_tp > 1 (with tp=1) gives the one-chip model the network
    the same seed makes under that tensor parallelism.
    Its record goes to stdout before any of its checks can raise, and
    everything it put on the devices is dropped before it returns."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPT, qkv_as_tp1
    from apex_tpu.monitor.compile import RecompileSentry
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=tp,
                                       devices=list(devices))
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    if weights_of_tp > 1:
        params = qkv_as_tp1(params, cfg, weights_of_tp)
    opt = FusedAdam(lr=LR, master_dtype=jnp.bfloat16)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    del params  # the donated state owns the only copy from here on
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, cfg.seq_len), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)

    # the jitted step reuses this executable: one compile, not two
    t0 = time.perf_counter()
    lowered = step.lower(state, tokens, labels)
    t1 = time.perf_counter()
    compiled = lowered.compile()   # XLA, or a read of the persistent cache
    compile_s = time.perf_counter() - t1
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    rec = {"devices": len(devices), "tp": tp, "dp": len(devices) // tp,
           "trace_lower_s": round(t1 - t0, 2),
           "compile_s": round(compile_s, 2),
           "tpu_custom_calls": text.count("tpu_custom_call"),
           "collectives": _collectives(text),
           "argument_bytes": int(mem.argument_size_in_bytes),
           "temp_bytes": int(mem.temp_size_in_bytes),
           "generated_code_bytes": int(mem.generated_code_size_in_bytes)}
    del lowered, compiled, text
    if require_chip and rec["tpu_custom_calls"] < 2:
        raise RuntimeError(
            f"{rec['tpu_custom_calls']} tpu_custom_call(s) in the step: "
            "flash attention or fused Adam silently took its jnp "
            "reference")

    sentry = RecompileSentry(step, name="chip_smoke_train", warn=False)
    losses, step_s = [], []
    for i in range(steps):
        if i == 2:
            sentry.mark_steady()
        t0 = time.perf_counter()
        state, loss = sentry(state, tokens, labels)
        loss = jax.block_until_ready(loss)
        step_s.append(round(time.perf_counter() - t0, 4))
        losses.append(float(loss))
    rec.update(losses=losses, step_s=step_s, sentry=sentry.summary())
    emit(phase="train_run", **rec)   # before any check can raise
    if not all(math.isfinite(v) for v in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    if sentry.steady_recompiles:
        raise RuntimeError(f"steady-state recompile: {sentry.events}")

    if len(devices) > 1:
        rec.update(_placement(state, list(devices), tp, require_chip))
        emit(phase="placement", tp=tp, bytes_in_use=rec["bytes_in_use"])
    del state, tokens, labels, step, sentry
    M.destroy_model_parallel()
    gc.collect()
    return rec


def _placement(state, devices, tp, require_chip) -> dict:
    """Are the shards really spread?  Every device holds 1/tp of the
    rows of each flat optimizer buffer, and no device holds more than
    twice the bytes of another."""
    for name in ("params", "exp_avg", "exp_avg_sq"):
        buf = getattr(state, name)
        rows = {s.device: s.data.shape[0] for s in buf.addressable_shards}
        if set(rows) != set(devices) or set(rows.values()) != {
                buf.shape[0] // tp}:
            raise RuntimeError(
                f"{name}: shards {rows} are not {buf.shape[0] // tp} rows "
                f"on each of {devices}")
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        if require_chip:
            raise RuntimeError(f"no memory_stats() from {devices}")
        return {"bytes_in_use": None}
    in_use = [int(s["bytes_in_use"]) for s in stats]
    if max(in_use) > 2 * min(in_use):
        raise RuntimeError(f"bytes_in_use is lopsided: {in_use}")
    return {"bytes_in_use": in_use}


def phase_train(cfg, devices, batch: int, steps: int, seed: int,
                require_chip: bool = True) -> dict:
    """The one-chip step: the first loss sits at ln(vocab), where a
    freshly initialised model's must, and the last is below it."""
    rec = train_run(cfg, devices[:1], 1, batch, steps, seed, require_chip)
    losses = rec["losses"]
    if abs(losses[0] - math.log(cfg.vocab_size)) >= LN_VOCAB_TOL:
        raise RuntimeError(
            f"first loss {losses[0]} is not within {LN_VOCAB_TOL} of "
            f"ln({cfg.vocab_size}) = {math.log(cfg.vocab_size):.3f}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"the loss did not fall: {losses}")
    return rec


def phase_multichip(cfg, devices, batch: int, steps: int, seed: int,
                    require_chip: bool = True) -> dict:
    """tp=2 x dp=2 with sequence parallelism over four devices, in the
    monolithic and the chunked collective spelling, against the same
    network and batch through the tp=1 step on the first device (the
    same seed, its packed QKV columns regrouped: models.gpt.qkv_as_tp1).
    The reference runs first: it needs its whole chip."""
    if len(devices) != 4:
        raise RuntimeError(f"needs four devices, got {len(devices)}")
    base = dataclasses.replace(cfg, sequence_parallel=False,
                               overlap_chunks=None)
    runs = {"tp1": train_run(base, devices[:1], 1, batch, steps, seed,
                             require_chip, weights_of_tp=2)}
    for name, chunks in (("monolithic", 1), ("chunked", 2)):
        sp = dataclasses.replace(cfg, sequence_parallel=True,
                                 overlap_chunks=chunks)
        runs[name] = train_run(sp, devices, 2, batch, steps, seed,
                               require_chip)
        if not sum(runs[name]["collectives"].values()):
            raise RuntimeError(f"{name}: no collective in the program")
    for a, b in (("monolithic", "tp1"), ("chunked", "tp1"),
                 ("chunked", "monolithic")):
        for i, (x, y) in enumerate(zip(runs[a]["losses"],
                                       runs[b]["losses"], strict=True)):
            if abs(x - y) > MULTICHIP_RTOL * abs(y):
                raise RuntimeError(
                    f"step {i}: {a} loss {x} and {b} loss {y} differ by "
                    f"more than rtol {MULTICHIP_RTOL}")
    return runs


# -------------------------------- serve --------------------------------

def phase_serve(eng, n_requests: int, min_prompt: int, max_new: int,
                seed: int) -> dict:
    """submit -> run on a built DecodeEngine, then hold every request's
    first greedy token to a plain full forward of the same weights over
    the prompt on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.models.gpt import GPT

    cfg, sc = eng.model_cfg, eng.serve_cfg
    rng = np.random.RandomState(seed)
    lens = rng.randint(min_prompt, sc.max_prompt_len + 1, n_requests)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]
    rids = [eng.submit(p, max_new) for p in prompts]
    t0 = time.perf_counter()
    finished = {f.request_id: f for f in eng.run()}
    run_s = time.perf_counter() - t0
    bad = [r for r in rids if r not in finished
           or finished[r].status != "ok"
           or len(finished[r].tokens) != max_new]
    if bad:
        raise RuntimeError(f"requests {bad} did not return {max_new} "
                           f"tokens with status ok: {finished}")
    if not eng.recompile_ok:
        raise RuntimeError("the decode step recompiled in steady state: "
                           f"{eng.sentry.events}")

    model = GPT(cfg)
    padded = np.zeros((n_requests, sc.max_prompt_len), np.int32)
    for row, p in zip(padded, prompts):
        row[:len(p)] = p   # causal: what follows a prompt cannot reach it
    mesh = Mesh(np.asarray(list(eng.params["pos_embed"].devices())),
                (cfg.axis_name,))
    forward = jax.jit(shard_map(
        lambda p, t: model.logits_local(p, model.apply(p, t)),
        mesh=mesh, in_specs=(model.partition_specs(), P()),
        out_specs=P(), check_vma=False))
    logits = forward(eng.params, jnp.asarray(padded))   # (S, B, V) fp32
    last = logits[jnp.asarray(lens) - 1, jnp.arange(n_requests)]
    top_v, top_i = (np.asarray(a) for a in jax.lax.top_k(last, 2))
    # two logits closer than four ulps of the compute dtype are a tie
    # the two spellings of the forward may break either way
    tie = 4 * float(jnp.finfo(cfg.dtype).eps) * np.abs(top_v[:, 0])
    first = np.asarray([finished[r].tokens[0] for r in rids])
    ok = (first == top_i[:, 0]) | ((first == top_i[:, 1])
                                   & (top_v[:, 0] - top_v[:, 1] < tie))
    rec = {"n_requests": n_requests, "prompt_lens": lens.tolist(),
           "max_new": max_new, "run_s": round(run_s, 2),
           "engine_steps": eng.steps_completed,
           "first_tokens": first.tolist(),
           "forward_top2": top_i.tolist(),
           "forward_top2_logits": top_v.round(4).tolist(),
           "top1_matches": int((first == top_i[:, 0]).sum()),
           "sentry": eng.sentry.summary()}
    emit(phase="serve_run", **rec)
    if not ok.all():
        raise RuntimeError(
            f"first tokens {first.tolist()} disagree with the full "
            f"forward's top-2 {top_i.tolist()}")
    return rec


# --------------------------------- main ---------------------------------

def _env_record(devices, cache_dir) -> dict:
    import importlib.metadata as md

    import jax

    from apex_tpu import csrc, tune

    so_found = os.path.exists(csrc._SO)
    native = csrc.available()
    stats = devices[0].memory_stats() or {}
    return {
        "note": "a smoke, not a benchmark",
        "jax": jax.__version__, "jaxlib": md.version("jaxlib"),
        "libtpu": md.version("libtpu"),
        "default_backend": jax.default_backend(),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
        "compile_cache_dir": cache_dir,
        "compile_cache_dir_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "csrc": ("found" if so_found else "built") if native else "python",
        "tune_device_kind": tune.device_kind(),
        "tune_cache_path": tune.cache_path(),
        "tune_cache_file_present": os.path.exists(tune.cache_path()),
        "tune_fingerprint": tune.fingerprint(),
        "memory_stats_keys": sorted(stats),
        "bytes_limit": stats.get("bytes_limit"),
    }


def _after(phase: str, device, earlier: dict, t0: float, **rec) -> None:
    from apex_tpu import tune

    stats = device.memory_stats() or {}
    emit(phase=phase, wall_s=round(time.perf_counter() - t0, 1),
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_in_use=stats.get("bytes_in_use"),
         tune=tune.stats(), **compile_account(earlier), **rec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip tp=2 x dp=2 step and "
                         "the one-chip step it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    n_used = 4 if args.multichip else 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": n_used}
    if devices[0].platform != "tpu" or len(devices) < n_used:
        emit(ok=False, device=dict(device, count=len(devices)),
             error=f"needs {n_used} TPU device(s); jax.devices() is "
                   f"{devices}")
        return 1
    try:
        _run(args, devices[:n_used])
    except BaseException as e:
        emit(ok=False, device=device, error=repr(e)[:2000])
        raise
    emit(ok=True, device=device)
    return 0


def _run(args, devices) -> None:
    from apex_tpu.monitor.compile import startup
    from apex_tpu.ops._common import on_chip
    from apex_tpu.utils.compile_cache import enable_compile_cache

    startup.arm()       # the kernels phase builds no mesh
    events = {}         # the ledger's totals at the phase before
    cache_dir = enable_compile_cache()
    if not on_chip():
        raise RuntimeError("apex_tpu's on_chip() is false on a TPU")
    emit(phase="env", **_env_record(devices, cache_dir))
    cfg = flagship_config()
    t0 = time.perf_counter()
    if args.multichip:
        phase_multichip(cfg, devices, BATCH, 3, args.seed)
        _after("multichip", devices[0], events, t0, rtol=MULTICHIP_RTOL)
        return
    phase_kernels(devices[0], args.seed)
    _after("kernels", devices[0], events, t0)

    t0 = time.perf_counter()
    phase_train(cfg, devices, BATCH, 5, args.seed)
    _after("train", devices[0], events, t0)

    from apex_tpu.serve import build_flagship_engine

    t0 = time.perf_counter()
    eng = build_flagship_engine(True, seed=args.seed)
    phase_serve(eng, n_requests=8, min_prompt=16, max_new=64,
                seed=args.seed)
    _after("serve", devices[0], events, t0, n_slots=eng.serve_cfg.n_slots)


if __name__ == "__main__":
    sys.exit(main())
