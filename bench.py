"""Benchmark: flagship GPT-350M-class training step on one TPU chip.

Prints ONE JSON line: tokens/sec/chip for a full fused training step
(fwd + bwd + FusedAdam) — the TPU counterpart of the reference's
"Average Iteration Time" GPT harness
(tests/L0/run_transformer/gpt_scaling_test.py:13-47) and the
images/sec Speed meter (examples/imagenet_amp.py ≡ main_amp.py:386-397).

The reference publishes no absolute numbers (BASELINE.md), so
`vs_baseline` is MEASURED in the same run against this framework's own
non-fused fp32 eager-style baseline: fp32 params/compute, dense
(S x S materialized) attention, per-leaf unfused Adam, no buffer
donation — the shape of a pre-apex training loop, ≡ the fused-vs-torch
comparisons the reference harnesses print
(apex/contrib/examples/multihead_attn/perf_test_multihead_attn.py:101-110).
Secondary keys in the same line: fused/unfused MHA latency and the
fused-optimizer step time.
"""

from __future__ import annotations

import contextlib
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


# per-config RecompileSentry summaries, stamped into the result JSON as
# "n_compiles" (ISSUE 5 satellite): a config whose steady state
# recompiles is measuring XLA, not training, and _time_steps raises
_SENTRY = {}


def _time_steps(step, state, tokens, labels, iters, warmup, name=None,
                call=None):
    """Time `iters` steady-state steps under the RecompileSentry.

    call: optional adapter `(sentry, state) -> (state, loss)` for
    steps whose signature is not `step(state, tokens, labels)` (the
    MoE step threads a batch tuple + aux) — the warmup/sync/steady
    measurement policy stays in this ONE place either way."""
    from apex_tpu.monitor.compile import RecompileSentry

    sentry = RecompileSentry(step, name=name or "bench", warn=False)
    if call is None:
        def call(s, st):
            return s(st, tokens, labels)
    for _ in range(warmup):
        state, loss = call(sentry, state)
    # the sentry replaces the old hand-rolled "warmup 2: donated-state
    # second compile" dance: keep warming (bounded) while the last call
    # still compiled, whatever the reason — layout recompiles included
    extra = 0
    while (extra < 3 and sentry.events
           and sentry.events[-1]["call"] == sentry.calls):
        state, loss = call(sentry, state)
        extra += 1
    jax.block_until_ready(loss)
    sentry.mark_steady()
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = call(sentry, state)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / iters
    if name:
        _SENTRY[name] = sentry.summary()
    if sentry.steady_recompiles:
        raise RuntimeError(
            f"{name or 'bench'}: {sentry.steady_recompiles} steady-state"
            f" recompile(s) during the timed window — the measurement is"
            f" compilation, not training; last signature: "
            f"{sentry.events[-1]['signature'][:120]}")
    return dt


def _fused_tokens_per_sec(on_tpu, batch, seq, cfg,
                          master_dtype=jnp.float32, name="gpt350m"):
    from apex_tpu.models.gpt import GPT
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4, use_pallas=on_tpu, master_dtype=master_dtype)
    opt_state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    del params  # donated state owns the master copy

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    iters, warmup = (20, 3) if on_tpu else (3, 1)
    dt = _time_steps(step, opt_state, tokens, labels, iters, warmup,
                     name=name)
    M.destroy_model_parallel()
    return batch * seq / dt


def _baseline_tokens_per_sec(on_tpu, batch, seq, cfg_fused):
    """Non-fused fp32 baseline: dense (S x S) attention, per-leaf
    unfused Adam (one jnp op chain per tensor, no flat buffer).  State
    is still donated — without it the three fp32 state copies alive per
    step thrash the allocator (11 s/iter at batch 1), which would
    measure the allocator, not the missing fusion."""
    import dataclasses

    from apex_tpu.models.gpt import GPT
    from apex_tpu.parallel import mesh as M

    cfg = dataclasses.replace(cfg_fused, dtype=jnp.float32,
                              logits_dtype=None,
                              use_flash_attention=False)
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def adam_leaf(p, g, m, v, step_t):
        b1, b2, eps, lr = 0.9, 0.999, 1e-8, 1e-4
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** step_t)
        vhat = v / (1 - b2 ** step_t)
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v

    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    specs = model.partition_specs()

    def local_step(state, tokens, labels):
        params, m, v, t = state
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, tokens, labels))(params)
        t = t + 1
        out = jax.tree.map(lambda p, g, mm, vv: adam_leaf(p, g, mm, vv, t),
                           params, grads, m, v)
        new_p = jax.tree.map(lambda o: o[0], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda o: o[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda o: o[2], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        return (new_p, new_m, new_v, t), loss

    zeros = jax.tree.map(jnp.zeros_like, params)
    state = (params, zeros, jax.tree.map(jnp.zeros_like, params),
             jnp.zeros((), jnp.int32))
    st_specs = (specs, specs, specs, P())
    step = jax.jit(shard_map(local_step, mesh=mesh,
                             in_specs=(st_specs, P(), P()),
                             out_specs=(st_specs, P()), check_vma=False),
                   donate_argnums=(0,))

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    # the recompile sentry inside _time_steps handles the donated-state
    # second compile (output layouts differing from the initial inputs)
    # by extending warmup while calls still compile — no hand-rolled
    # "warmup 2" needed, and a steady-state recompile now raises
    # instead of silently polluting the measurement
    iters, warmup = (3, 1) if on_tpu else (2, 1)
    dt = _time_steps(step, state, tokens, labels, iters, warmup,
                     name="baseline")
    M.destroy_model_parallel()
    return batch * seq / dt


def _baseline_best(on_tpu, batch, seq, cfg_fused):
    """fp32 state + activations need ~3x the fused path's HBM; fall back
    to smaller batches (tokens/s is per-token, so comparable) before
    giving up."""
    import gc

    err = "no batch attempted"
    # fp32 state + activations are ~3-4x the fused path's footprint:
    # batch/2 nominally fits but XLA spills and measures the allocator
    # (~15x slowdown observed), so start where there is real headroom
    b = max(1, batch // 4)
    while b >= 1:
        try:
            return _baseline_tokens_per_sec(on_tpu, b, seq, cfg_fused), b
        except Exception as e:
            # keep only the message: the traceback would pin the failed
            # attempt's multi-GB buffers across the retry
            err = repr(e)
            b //= 2
            gc.collect()
    raise RuntimeError(err)


def _mha_latencies(on_tpu):
    """Fused (flash kernel) vs unfused (dense jnp) attention fwd+bwd ms
    at B8 H16 S2048 D64 ≡ perf_test_multihead_attn's timing loop."""
    from apex_tpu.ops.flash_attention import (
        attention_reference,
        flash_attention,
    )
    B, H, S, D = (8, 16, 2048, 64) if on_tpu else (2, 2, 256, 64)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
               for kk in ks)

    def timed(fn):
        g = jax.jit(jax.grad(
            lambda q, k, v: fn(q, k, v).astype(jnp.float32).mean(),
            argnums=(0, 1, 2)))
        out = g(q, k, v)
        jax.block_until_ready(out)
        iters = 10 if on_tpu else 2
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    fused = timed(functools.partial(flash_attention, causal=True))
    unfused = timed(functools.partial(attention_reference, causal=True))
    return fused, unfused


def _gpt1p3b_tokens_per_sec(on_tpu):
    """1.3B single-chip config (VERDICT r2 #1): h2048 L24 H32, batch 7 x
    seq 512, bf16 Adam state (p+m+v at 6 B/param fits one 16 GB chip),
    NO remat (b7 activations fit; the round-5 sweep: b8 dots 13.24k,
    b8 no-remat 13.17k, b7 no-remat 13.35k, names:all5 13.13k — the
    step is component-bound, not remat-bound; docs/PERF.md anatomy),
    bf16 LM-head logits."""
    from apex_tpu.models.gpt import GPT2_1p3B, GPTConfig
    if on_tpu:
        batch, seq = 7, 512
        cfg = GPTConfig(vocab_size=50304, seq_len=seq, dropout=0.0,
                        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
                        remat=False,
                        use_flash_attention=True, **GPT2_1p3B)
    else:
        batch, seq = 2, 64
        cfg = GPTConfig(vocab_size=512, seq_len=seq, hidden=64,
                        num_layers=2, num_heads=4, dropout=0.0,
                        remat=True, remat_policy="dots")
    return _fused_tokens_per_sec(on_tpu, batch, seq, cfg,
                                 master_dtype=jnp.bfloat16,
                                 name="gpt1p3b")


def _bert_seq_per_sec(on_tpu):
    """BERT-Large MLM+NSP step with FusedLAMB (VERDICT r2 #5): flash
    padding-masked attention + MXU segment-sum trust ratios.  Round-3
    anatomy in docs/PERF.md: round 4 = 101 seq/s ~= 53% MFU at
    b32 x s512 with bf16 LAMB state."""
    from apex_tpu.models.bert import Bert, BertConfig
    from apex_tpu.optimizers.fused_lamb import FusedLAMB
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    # batch 32: LAMB exists FOR large batches — the optimizer pass
    # amortizes (b8: 79 seq/s, b16: 94.5, b32: 101; b64 fails compile),
    # bf16 master state halves the LAMB pass HBM traffic (round 4)
    batch, seq = (32, 512) if on_tpu else (2, 64)
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    cfg = (BertConfig(seq_len=seq, dtype=jnp.bfloat16,
                      use_flash_attention=True) if on_tpu else
           BertConfig(seq_len=seq, hidden=128, num_layers=2, num_heads=4,
                      dtype=jnp.bfloat16))
    model = Bert(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # standard BERT recipe: no weight decay for bias/LayerNorm params
    # (≡ _get_params_for_weight_decay_optimization's two param groups)
    from apex_tpu.transformer.pipeline_parallel.common import (
        get_params_for_weight_decay_optimization,
    )
    wd_mask = get_params_for_weight_decay_optimization(params)
    opt = FusedLAMB(lr=1e-4, weight_decay=0.01, use_pallas=on_tpu,
                    master_dtype=jnp.bfloat16 if on_tpu else jnp.float32,
                    wd_mask=wd_mask)
    opt_state = init_sharded_optimizer(opt, model, params, mesh)
    del params
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    mlm_labels = jnp.roll(tokens, -1, axis=1)
    loss_mask = jax.random.bernoulli(jax.random.PRNGKey(2), 0.15,
                                     (batch, seq))
    nsp = jax.random.randint(jax.random.PRNGKey(3), (batch,), 0, 2)

    def loss_fn(p, t, l):
        return model.loss(p, t, l, loss_mask, nsp_labels=nsp)

    step = make_tp_dp_train_step(model, opt, mesh, loss_fn=loss_fn,
                                 donate=True)
    iters, warmup = (10, 2) if on_tpu else (2, 1)
    dt = _time_steps(step, opt_state, tokens, mlm_labels, iters, warmup,
                     name="bert")
    M.destroy_model_parallel()
    return batch / dt


def _resnet50_img_per_sec(on_tpu):
    """ResNet-50 AMP-O1 fused train step, synthetic data, batch 256 —
    the Speed meter of the reference's canonical example
    (examples/imagenet/main_amp.py:386-397; see examples/imagenet_amp.py
    for the full training loop).  Round-3 measurement: 1,649 img/s/chip
    (docs/PERF.md) — this puts it in the driver JSON."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models.resnet import ResNet
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
    from apex_tpu.optimizers.fused_sgd import FusedSGD
    from apex_tpu.parallel import ddp
    from apex_tpu.parallel import mesh as M

    batch, size, arch = (256, 224, "resnet50") if on_tpu else \
        (4, 32, "resnet18")
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    # space_to_depth stem computes the IDENTICAL function (exact weight
    # rewrite, models/resnet.py) ~5 ms/step faster on v5e; round 5 also
    # moved BN batch stats off the Pallas welford kernel onto XLA's
    # fused reductions (ops/welford.py) — together 1,665 -> 2,305-2,319
    # img/s (3 runs; docs/PERF.md has the per-layer anatomy)
    model = ResNet(arch, num_classes=1000, axis_name="dp",
                   stem="space_to_depth" if on_tpu else "conv7")
    params, mstate = model.init(jax.random.PRNGKey(0))
    amp_state = amp.initialize(opt_level="O1")

    def loss_fn(p, ms, b):
        x, y = b
        logits, new_ms = model.apply(p, ms, x, training=True)
        return jnp.mean(softmax_cross_entropy_loss(
            logits.astype(jnp.float32), y)), new_ms

    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    state = opt.init(params)
    scaler = amp_state.loss_scalers[0]
    step = ddp.make_train_step(loss_fn, opt, mesh, amp_state=amp_state,
                               batch_spec=(P("dp"), P("dp")),
                               with_state=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, size, size, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000)
    iters, warmup = (20, 3) if on_tpu else (2, 1)
    for _ in range(warmup):
        state, scaler, mstate, loss = step(state, scaler, mstate, (x, y))
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, scaler, mstate, loss = step(state, scaler, mstate, (x, y))
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / iters
    M.destroy_model_parallel()
    return batch / dt


def _long_context_32k(on_tpu):
    """32k-token causal flash attention fwd+bwd on one chip (B1 H8 D64)
    — the long-context kernel north star (VERDICT r4 next-#4; dense
    attention cannot represent this: the bf16 score matrix alone would
    be 17 GB).  Returns (ms, tokens/s)."""
    from apex_tpu.ops.flash_attention import flash_attention

    B, H, S, D = (1, 8, 32768, 64) if on_tpu else (1, 2, 1024, 32)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
               for kk in ks)

    g = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True).astype(
            jnp.float32).mean(), argnums=(0, 1, 2)))
    out = g(q, k, v)
    jax.block_until_ready(out)
    iters = 5 if on_tpu else 2
    t0 = time.perf_counter()
    for _ in range(iters):
        out = g(q, k, v)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    return dt * 1e3, B * S / dt


def _zero2_bucket_sweep(on_tpu):
    """ZeRO-2 DistributedFusedAdam wired through ddp.make_train_step
    (ISSUE 3 satellite): sweep the n_buckets backward-overlap knob over
    the local dp axis.  With one chip dp=1 — the sweep still exercises
    the per-bucket reduce-scatter/update/gather pipeline structure, and
    on multi-chip runs it measures the real overlap.  Returns
    {"dp": world, "tokens_per_sec": {n_buckets: value}}."""
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.optimizers.distributed_fused_adam import (
        DistributedFusedAdam,
    )
    from apex_tpu.parallel import ddp
    from apex_tpu.parallel import mesh as M
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if on_tpu:
        batch, seq = 8, 1024
        cfg = GPTConfig(vocab_size=50304, seq_len=seq, hidden=1024,
                        num_layers=8, num_heads=16, dropout=0.0,
                        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
                        use_flash_attention=True)
    else:
        batch, seq = 2, 64
        cfg = GPTConfig(vocab_size=512, seq_len=seq, hidden=64,
                        num_layers=2, num_heads=4, dropout=0.0)
    out = {}
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel()
    dp = mesh.devices.size
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)

    def loss_fn(p, b):
        return model.loss(p, b[0], b[1])

    for nb in (1, 2, 4):
        opt = DistributedFusedAdam(
            num_shards=dp, lr=1e-4, n_buckets=nb,
            use_pallas=on_tpu or None,
            master_dtype=jnp.bfloat16 if on_tpu else jnp.float32)
        sspec = opt.state_partition_specs()
        # one-shot sharded init per bucket config: each nb is a fresh
        # optimizer, so the per-iteration jit is inherent, not a leak
        state = jax.jit(shard_map(  # lint: disable=HS405
            opt.init, mesh=mesh, in_specs=(P(),), out_specs=sspec,
            check_vma=False))(params)
        step = ddp.make_train_step(loss_fn, opt, mesh,
                                   batch_spec=(P("dp"), P("dp")))
        iters, warmup = (10, 2) if on_tpu else (2, 1)
        for _ in range(warmup):
            state, _, loss = step(state, None, (tokens, labels))
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, _, loss = step(state, None, (tokens, labels))
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / iters
        out[str(nb)] = round(batch * seq / dt, 1)
        del state
    M.destroy_model_parallel()
    return {"dp": dp, "tokens_per_sec": out}


def _serve_decode_bench(on_tpu):
    """Continuous-batching decode throughput + per-token latency at N
    concurrent ragged streams (ISSUE 8 — the serving bench axes the
    "millions of users" north star is judged by).  Each concurrency
    level builds the flagship serve engine (apex_tpu.serve; GPT-350M
    weights on TPU, the smoke config on CPU), submits N ragged-length
    prompts, and drives the engine to completion through
    `serve.measure_decode` — the shared drive-and-measure helper
    (examples/serve_gpt.py quotes the same convention): device-synced
    per-step timing, throughput over tokens ACTUALLY emitted, p50/p99
    per-token latency over pure decode steps with admission/
    retirement churn steps excluded.  The RecompileSentry verdict
    rides out as `recompile_ok` — False means churn retraced the
    decode step, which is a correctness bug, not a perf number."""
    import numpy as np

    from apex_tpu.serve import build_flagship_engine, measure_decode

    streams = (1, 8, 64, 256) if on_tpu else (1, 8)
    sweep = {}
    params = None                   # one flagship init, shared by the sweep
    for n in streams:
        eng = build_flagship_engine(on_tpu, n_slots=n, params=params)
        params = eng.params
        rng = np.random.RandomState(0)
        mp = eng.serve_cfg.max_prompt_len
        max_new = eng.serve_cfg.max_new_cap if on_tpu else 8
        for _ in range(n):
            plen = int(rng.randint(1, mp + 1))
            eng.submit(rng.randint(
                0, eng.model_cfg.vocab_size, plen).tolist(), max_new)
        m = measure_decode(eng, max_steps=16 * max_new + 64)
        entry = {
            "tokens_per_sec": round(m["tokens_per_sec"], 1),
            "p50_ms": round(m["p50_ms"], 3),
            "p99_ms": round(m["p99_ms"], 3),
            "steps": m["steps"],
            "churn_steps": m["churn_steps"],
            "recompile_ok": m["recompile_ok"],
        }
        # the request-lifecycle ledger summary (ISSUE 10): per-level
        # TTFT / queue-wait / per-token percentiles + pool/queue peaks
        # ride under the unreserved `serving` dict; _stamp_serve lifts
        # the largest-N scalars into the flat v7 `serve_*` fields
        if eng.telemetry is not None:
            led = eng.telemetry.ledger

            def ms(v):
                return None if v is None else round(1e3 * v, 3)
            entry["ledger"] = {
                "requests": led.n_retired,
                "tokens": led.tokens_emitted,
                "ttft_p50_ms": ms(led.ttft.percentile(50.0)),
                "ttft_p99_ms": ms(led.ttft.percentile(99.0)),
                "token_p50_ms": ms(led.token_lat.percentile(50.0)),
                "token_p99_ms": ms(led.token_lat.percentile(99.0)),
                "queue_wait_p99_ms": ms(led.queue_wait.percentile(99.0)),
                "queue_wait_max_ms": ms(led.queue_wait.max),
                "pool_util_peak": round(
                    eng.telemetry.peaks["pool_util"], 4),
                "queue_depth_peak": eng.telemetry.peaks["queue_depth"],
            }
        sweep[str(n)] = entry
    return sweep


def _serve_overload_bench(on_tpu):
    """The overload leg (ISSUE 14): a 4x-slot-capacity storm with
    mixed deadlines against a BOUNDED admission queue — what the
    serving plane does when traffic exceeds it, measured instead of
    assumed.  Stamps (via _stamp_serve_overload): `serve_shed_fraction`
    (shed+expired fraction of submissions — how much the engine
    refused to protect the rest) and `serve_goodput_tokens_per_sec`
    (tokens of requests that completed OK per wall second — the
    number overload control exists to protect; contrast with
    `serve_decode_tokens_per_sec`, which is raw decode throughput
    under healthy load).  The ledger's terminal-state balance and the
    page-pool reconciliation are correctness gates: a False voids the
    stamp."""
    import time as _t

    import numpy as np

    from apex_tpu.serve import build_flagship_engine
    from apex_tpu.serve.engine import flagship_n_slots

    n_slots = flagship_n_slots(on_tpu)
    eng = build_flagship_engine(
        on_tpu, serve_overrides={"max_queue_depth": 2 * n_slots,
                                 "shed_policy": "shed-lowest-deadline"})
    n_requests = 4 * n_slots
    max_new = eng.serve_cfg.max_new_cap if on_tpu else 8
    rng = np.random.RandomState(0)
    mp = eng.serve_cfg.max_prompt_len
    t0 = _t.perf_counter()
    for i in range(n_requests):
        plen = int(rng.randint(1, mp + 1))
        budget = int(rng.randint(1, max_new + 1))
        # mixed deadlines: half the storm carries a finite deadline
        # (the shed policy's victim-ordering pool), half is unbounded
        dl = 120_000.0 if i % 2 else None
        eng.submit(rng.randint(0, eng.model_cfg.vocab_size,
                               plen).tolist(), budget, deadline_ms=dl)
    fins = {}
    steps = 0
    while eng.pending:
        if steps >= n_requests * max_new + 64:
            raise RuntimeError("overload storm did not drain")
        eng.step()
        for f in eng.poll():
            fins[f.request_id] = f
        steps += 1
    wall = _t.perf_counter() - t0
    led = eng.telemetry.ledger
    good_tokens = sum(len(f.tokens) for f in fins.values()
                      if f.status == "ok")
    return {
        "n_requests": n_requests,
        "n_ok": led.n_retired,
        "n_shed": led.n_shed,
        "n_expired": led.n_expired,
        "shed_fraction": (led.n_shed + led.n_expired) / n_requests,
        "goodput_tokens_per_sec": round(good_tokens / wall, 1),
        "good_tokens": good_tokens,
        "steps": steps,
        "balance_ok": led.balance()["ok"],
        "pool_reconciled": (eng.cache.free_pages
                            == eng.kv_config.usable_pages),
        "recompile_ok": eng.recompile_ok,
        "queue_saturation_peak": round(
            eng.telemetry.peaks["queue_saturation"], 4),
    }


def _stamp_serve_overload(result, leg):
    """Flat v10 overload scalars + the dict under `serving_overload`.
    The correctness gates (balance/pool/sentry) must hold for the
    stamps to land — a storm that corrupted accounting has no
    goodput number worth publishing."""
    result["serving_overload"] = leg
    if (leg["balance_ok"] and leg["pool_reconciled"]
            and leg["recompile_ok"]):
        result["serve_shed_fraction"] = float(leg["shed_fraction"])
        result["serve_goodput_tokens_per_sec"] = float(
            leg["goodput_tokens_per_sec"])


def _stamp_serve(result, sweep):
    """Fold the serve sweep into the result JSON: the full dict under
    `serving` (deliberately OUTSIDE the `serve_` prefix — that prefix
    is reserved for JSON scalars by SCHEMA v5, the `comms_` rule) and
    the flat `serve_*` scalars from the LARGEST concurrency (the
    headline serving number).  The recompile verdict is the AND over
    the whole sweep — one churned concurrency poisons the stamp,
    deliberately."""
    result["serving"] = sweep
    top_n = max(sweep, key=int)
    top = sweep[top_n]
    result["serve_streams"] = int(top_n)
    result["serve_decode_tokens_per_sec"] = float(top["tokens_per_sec"])
    result["serve_p50_ms"] = float(top["p50_ms"])
    result["serve_p99_ms"] = float(top["p99_ms"])
    result["serve_recompile_ok"] = all(
        v["recompile_ok"] for v in sweep.values())
    # v7 (ISSUE 10): the largest-N ledger scalars — TTFT percentiles,
    # queue-wait p99, and the run's PEAK pool utilization.  The peak
    # gets its OWN field (`serve_pool_util_peak`): the live logger
    # stamps `serve_pool_util` as an instantaneous gauge, and one
    # field must not carry two semantics (the re-semanticize rule,
    # docs/observability.md).  Optional-never-null: a sweep without
    # ledger data (telemetry off) simply doesn't stamp them.
    led = top.get("ledger") or {}
    for src, dst in (("ttft_p50_ms", "serve_ttft_p50_ms"),
                     ("ttft_p99_ms", "serve_ttft_p99_ms"),
                     ("queue_wait_p99_ms", "serve_queue_wait_p99_ms"),
                     ("pool_util_peak", "serve_pool_util_peak")):
        v = led.get(src)
        if v is not None:
            result[dst] = float(v)


def _ckpt_cycle(on_tpu):
    """One async save → elastic restore cycle of the flagship ZeRO-2
    training state (ISSUE 9): prices the checkpoint cadence for the
    bench JSON.  Uses the same dp-sharded GPT config as the zero2
    bucket sweep (the shard-native path is what the tentpole is for;
    the replicated flagship state saves through the identical
    manager).  Stamps, via _stamp_ckpt: `ckpt_save_s` (writer-thread
    wall clock), `ckpt_blocking_s` (what the hot path paid —
    device→host snapshot; the write itself ran in the background),
    `ckpt_bytes`, restore seconds, and a bitwise round-trip verdict
    (False = the checkpoint that was just priced does not reproduce
    the state, which voids the number)."""
    import shutil
    import tempfile

    from apex_tpu.checkpoint import CheckpointManager
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.optimizers.distributed_fused_adam import (
        DistributedFusedAdam,
    )
    from apex_tpu.parallel import ddp
    from apex_tpu.parallel import mesh as M
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if on_tpu:
        batch, seq = 8, 1024
        cfg = GPTConfig(vocab_size=50304, seq_len=seq, hidden=1024,
                        num_layers=8, num_heads=16, dropout=0.0,
                        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
                        use_flash_attention=True)
    else:
        batch, seq = 2, 64
        cfg = GPTConfig(vocab_size=512, seq_len=seq, hidden=64,
                        num_layers=2, num_heads=4, dropout=0.0)
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel()
    dp = mesh.devices.size
    # batch must shard over dp (the comms_probe divisibility rule)
    batch = -(-batch // dp) * dp
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = DistributedFusedAdam(
        num_shards=dp, lr=1e-4, n_buckets=2, use_pallas=on_tpu or None,
        master_dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    sspec = opt.state_partition_specs()
    state = jax.jit(shard_map(
        opt.init, mesh=mesh, in_specs=(P(),), out_specs=sspec,
        check_vma=False))(params)
    step = ddp.make_train_step(
        lambda p, b: model.loss(p, b[0], b[1]), opt, mesh,
        batch_spec=(P("dp"), P("dp")))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    state, _, loss = step(state, None, (tokens, labels))
    jax.block_until_ready(loss)

    tmpd = tempfile.mkdtemp(prefix="apex_ckpt_bench_")
    try:
        mgr = CheckpointManager(tmpd, opt, every_n_steps=1)
        mgr.save(1, state)
        mgr.wait()
        st = mgr.stats()
        t0 = time.perf_counter()
        restored, _, _ = mgr.restore(mesh)
        jax.block_until_ready(restored)
        restore_s = time.perf_counter() - t0
        # EVERY state field: a verdict that only checked the params
        # would stamp ok=True over damaged moment shards
        ok = all(
            bool(np.array_equal(np.asarray(getattr(restored, f)),
                                np.asarray(getattr(state, f))))
            for f in state._fields)
    finally:
        shutil.rmtree(tmpd, ignore_errors=True)
    M.destroy_model_parallel()
    return {"dp": dp, "save_s": st["ckpt_save_s"],
            "blocking_s": st["ckpt_blocking_s"],
            "bytes": st["ckpt_bytes"],
            "restore_s": round(restore_s, 6), "roundtrip_ok": ok}


def _stamp_ckpt(result, cycle):
    """Flat v6 `ckpt_*` scalars (the prefix is JSON-scalar-reserved,
    the `comms_`/`serve_` rule) + the full cycle dict under
    `checkpointing`."""
    result["checkpointing"] = cycle
    result["ckpt_save_s"] = float(cycle["save_s"])
    result["ckpt_blocking_s"] = float(cycle["blocking_s"])
    result["ckpt_bytes"] = int(cycle["bytes"])
    result["ckpt_restore_s"] = float(cycle["restore_s"])
    result["ckpt_roundtrip_ok"] = bool(cycle["roundtrip_ok"])


def _fleet_cycle(on_tpu):
    """Multi-host commit + kill + elastic-resume mini-cycle (ISSUE 11):
    two emulated hosts commit one ZeRO-layout checkpoint through the
    sub-manifest → rank-0 barrier protocol, a half-fleet commit is
    REFUSED, and the `ElasticOrchestrator` drives one lost-rank
    recovery whose re-shard restore must reproduce the committed
    canonical flat bitwise.  Protocol-level (host arrays, no jit) so
    the stamp is cheap on every backend; the full fleet gate with real
    process kills is `scripts/fleet_probe.py`.  Stamps, via
    _stamp_fleet: `fleet_resume_ok`, `fleet_resumes`,
    `ckpt_commit_barrier_s` (schema v8)."""
    import shutil
    import tempfile

    from apex_tpu.checkpoint import ElasticOrchestrator
    from apex_tpu.checkpoint import multihost as MH
    from apex_tpu.checkpoint import sharded as S
    from apex_tpu.checkpoint.chaos import RankLostError

    dp = 4
    n = (1 << 20 if on_tpu else 1 << 12)
    layout = {"align": 64, "total": n, "n_tensors": 1, "num_shards": dp,
              "n_buckets": 1, "bucket_totals": [n], "bucket_padded": [n],
              "master_dtype": "float32"}
    rng = np.random.RandomState(11)
    flat = rng.randn(n).astype(np.float32)
    shards = {r: flat[r * n // dp:(r + 1) * n // dp] for r in range(dp)}
    tmp = tempfile.mkdtemp(prefix="apex_fleet_bench_")
    try:
        # 2-host commit: host 1's half, then host 0 commits
        MH.save_sharded_multihost(
            tmp, 1, {"params_shard": ("sharded",
                                      {2: shards[2], 3: shards[3]})},
            process_id=1, num_processes=2, flat_layout=layout)
        _, barrier_s = MH.save_sharded_multihost(
            tmp, 1, {"params_shard": ("sharded",
                                      {0: shards[0], 1: shards[1]})},
            process_id=0, num_processes=2, flat_layout=layout,
            timeout_s=30.0)
        # half-fleet commit of step 2 must be REFUSED (host 1 "dead")
        refused = False
        try:
            MH.save_sharded_multihost(
                tmp, 2, {"params_shard": ("sharded",
                                          {0: shards[0], 1: shards[1]})},
                process_id=0, num_processes=2, flat_layout=layout,
                timeout_s=0.2, poll_s=0.02)
        except MH.MultihostCommitError:
            refused = True
        refused = refused and S.latest_committed_step(tmp) == 1

        # one lost-rank recovery: session 1 dies, session 2 re-shards
        # the committed step to dp=2 and hands back the canonical flat
        dst = dict(layout, num_shards=2)

        def build(new_dp, resume_step, attempt):
            def session():
                if new_dp == dp:
                    raise RankLostError("rank 3 lost (bench cycle)",
                                        rank=3)
                p = S.step_dir(tmp, resume_step)
                m = S.read_manifest(p)
                host = S.load_field_host(p, m, "params_shard",
                                         check_crc=True)
                re2 = S.reshard(host, m["flat_layout"], dst)
                return S.canonical_flat(list(np.split(re2, 2)), dst)
            return session

        orch = ElasticOrchestrator(tmp, build, initial_dp=dp,
                                   choose_dp=lambda d, e: 2)
        canon = orch.run()
        resume_ok = bool(np.array_equal(canon, flat))
        return {"dp": dp, "n_hosts": 2,
                "barrier_s": round(barrier_s, 6),
                "refused_ok": bool(refused),
                "resumes": orch.stats()["fleet_resumes"],
                "resume_ok": resume_ok}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _stamp_fleet(result, cycle):
    """Flat v8 `fleet_*` / barrier scalars (prefix JSON-scalar-reserved,
    the `ckpt_` rule) + the full cycle dict under `fleet`."""
    result["fleet"] = cycle
    result["fleet_resume_ok"] = bool(cycle["resume_ok"]
                                     and cycle["refused_ok"])
    result["fleet_resumes"] = int(cycle["resumes"])
    result["ckpt_commit_barrier_s"] = float(cycle["barrier_s"])


def _moe_gpt_bench(on_tpu):
    """Expert-parallel MoE-GPT training throughput (ISSUE 13): the
    flagship `models/moe_gpt.py` step — fp32 top-k router, capacity-
    factor dispatch into the static (E, C, H) buffer, ONE all_to_all
    over the ep axis each way, ZeRO-2 master state over the combined
    (dp, ep) axes — built by the SAME shared builder the lint/comms
    gates trace (`build_moe_train_step`; ep=2 on any even device
    count, CPU smoke shapes off-TPU) and timed under the
    RecompileSentry (a routing-dependent recompile would measure XLA,
    not training — the zero-steady-recompile acceptance criterion).
    Returns the dict `_stamp_moe` folds into the result: tokens/s plus
    the last step's aux scalars (drop fraction, load-balance loss,
    gate entropy)."""
    from apex_tpu.models.moe_gpt import build_moe_train_step
    from apex_tpu.parallel import mesh as M

    model, step, args, info = build_moe_train_step(on_tpu)
    state, _, (tok_sds, _) = args
    tokens = jax.random.randint(jax.random.PRNGKey(1), tok_sds.shape,
                                0, info["vocab_size"])
    labels = jnp.roll(tokens, -1, axis=1)
    iters, warmup = (20, 3) if on_tpu else (3, 1)
    last = {}

    def call(sentry, st):
        st, _, loss, aux = sentry(st, None, (tokens, labels))
        last["aux"] = aux
        return st, loss

    dt = _time_steps(step, state, None, None, iters, warmup,
                     name="moe_gpt", call=call)
    aux_host = {k: float(v)
                for k, v in jax.device_get(last["aux"]).items()}
    M.destroy_model_parallel()
    cfg = info["config"]
    return {
        "tokens_per_sec": round(info["batch"] * info["seq"] / dt, 1),
        "dp": info["dp"], "ep": info["ep"],
        "n_experts": cfg.n_experts, "top_k": cfg.top_k,
        "capacity_factor": cfg.capacity_factor,
        "drop_fraction": round(aux_host["moe_drop_fraction"], 6),
        "aux_loss": round(aux_host["moe_aux_loss"], 6),
        "gate_entropy": round(aux_host["moe_gate_entropy"], 6),
        "z_loss": round(aux_host["moe_z_loss"], 6),
    }


def _stamp_moe(result, d):
    """Flat v9 `moe_*` scalars (the prefix is JSON-scalar-reserved,
    the `comms_`/`serve_` rule) + the full dict under `moe_gpt`."""
    result["moe_gpt"] = d
    result["moe_tokens_per_sec"] = float(d["tokens_per_sec"])
    result["moe_drop_fraction"] = float(d["drop_fraction"])
    result["moe_aux_loss"] = float(d["aux_loss"])
    result["moe_gate_entropy"] = float(d["gate_entropy"])
    result["moe_z_loss"] = float(d["z_loss"])


def _overlap_measure(on_tpu):
    """Chunked-vs-monolithic TP step latency (ISSUE 18): the tp=2
    sequence-parallel GPT step — the SAME model/optimizer build as the
    comms/timeline probes' `gpt_tp_overlap` flagship — timed in BOTH
    collective spellings.  `overlap_chunks=1` keeps the ORIGINAL
    monolithic all-gather / reduce-scatter program (byte-identical HLO
    to the pre-chunking layers); `overlap_chunks=2` decomposes the
    column-parallel gather into a ppermute ring interleaved with
    partial GEMMs and chunks the row-parallel reduce-scatter.  Both
    legs run under the RecompileSentry; the speedup ratio is the
    number the chunking exists to move (>1 only where the backend
    actually runs collectives async — CPU rings add pure per-chunk
    latency, the honest c*alpha floor docs/PERF.md prices)."""
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    chunks = 2
    out = {"tp": 2, "chunks": chunks}
    iters, warmup = (20, 3) if on_tpu else (3, 1)
    for spelling, c in (("monolithic", 1), ("chunked", chunks)):
        if on_tpu:
            batch, seq = 12, 1024
            cfg = GPTConfig(vocab_size=50304, seq_len=seq, hidden=1024,
                            num_layers=24, num_heads=16, dropout=0.0,
                            dtype=jnp.bfloat16,
                            logits_dtype=jnp.bfloat16, remat=False,
                            use_flash_attention=True,
                            sequence_parallel=True, overlap_chunks=c)
        else:
            batch, seq = 2, 64
            cfg = GPTConfig(vocab_size=512, seq_len=seq, hidden=64,
                            num_layers=2, num_heads=4, dropout=0.0,
                            sequence_parallel=True, overlap_chunks=c)
        M.destroy_model_parallel()
        mesh = M.initialize_model_parallel(tensor_model_parallel_size=2)
        dp = mesh.devices.size // 2
        batch = -(-batch // max(1, dp)) * max(1, dp)
        model = GPT(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-4, use_pallas=on_tpu,
                        master_dtype=jnp.bfloat16 if on_tpu
                        else jnp.float32)
        state = init_sharded_optimizer(opt, model, params, mesh)
        step = make_tp_dp_train_step(model, opt, mesh, donate=True)
        del params
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, seq), 0, cfg.vocab_size)
        labels = jnp.roll(tokens, -1, axis=1)
        dt = _time_steps(step, state, tokens, labels, iters, warmup,
                         name=f"gpt_tp_overlap_{spelling}")
        out[f"{spelling}_step_ms"] = round(dt * 1e3, 3)
        out[f"{spelling}_tokens_per_sec"] = round(batch * seq / dt, 1)
        M.destroy_model_parallel()
    out["speedup"] = round(
        out["monolithic_step_ms"] / out["chunked_step_ms"], 3)
    return out


def _stamp_overlap(result, d):
    """Flat `overlap_*` scalars for the chunked-TP leg + the full dict
    under `tp_overlap`.  Bench-result-only keys: `overlap_` is NOT one
    of the logger's reserved record prefixes — these never ride a
    MetricsLogger record, so SCHEMA_VERSION stays at 11."""
    result["tp_overlap"] = d
    result["overlap_chunks"] = int(d["chunks"])
    result["overlap_monolithic_step_ms"] = float(d["monolithic_step_ms"])
    result["overlap_chunked_step_ms"] = float(d["chunked_step_ms"])
    result["overlap_step_speedup"] = float(d["speedup"])


def _adam_1b_step_ms(on_tpu):
    """Fused flat-buffer Adam step at 1B params (fp32 p/m/v, bf16
    grads) — the large-param optimizer north star (BASELINE.md;
    ≡ tests/L0/run_optimizers scale point).  Round-3: 44.4 ms ≈ 721
    GB/s effective (docs/PERF.md)."""
    from apex_tpu.ops import optimizer_kernels as K

    n = 10 ** 9 if on_tpu else 10 ** 6
    n = -(-n // K.FLAT_TILE) * K.FLAT_TILE
    p = jnp.zeros((n,), jnp.float32)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    g = jnp.full((n,), 1e-3, jnp.bfloat16)

    def _step(p, m, v, g):
        return K.adam_flat(p, m, v, g, lr=1e-3, step=10,
                           weight_decay=0.01,
                           use_pallas_override=on_tpu or None)

    step = jax.jit(_step, donate_argnums=(0, 1, 2))
    iters, warmup = (20, 3) if on_tpu else (3, 1)
    for _ in range(warmup):
        p, m, v = step(p, m, v, g)
    jax.block_until_ready(p)
    t0 = time.perf_counter()
    for _ in range(iters):
        p, m, v = step(p, m, v, g)
    jax.block_until_ready(p)
    return (time.perf_counter() - t0) / iters * 1e3


def _timeline_anatomy(on_tpu, batch, seq, cfg, master_dtype):
    """Measured runtime anatomy of the flagship program (ISSUE 15):
    the SAME tp_dp step `_compile_audit_350m` audits, executed for two
    warmup + three captured steady steps under a `ProfileCapture`, the
    trace parsed by `monitor.timeline`.  Returns the v11 `timeline_*`
    stamps + the full report dict.  Runs in its OWN `_timed` key, the
    compile_audit rule: trace capture adds profiler overhead to every
    step it wraps, and parsing walks the whole event list — neither
    may land inside a timed metric window the bench keeps comparable
    across rounds."""
    import tempfile

    from apex_tpu import monitor
    from apex_tpu.models.gpt import GPT
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4, use_pallas=on_tpu, master_dtype=master_dtype)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    del params
    tok = jnp.zeros((batch, seq), jnp.int32)
    cap = monitor.profile_capture(
        range(3), logdir=tempfile.mkdtemp(prefix="bench_timeline_"))
    try:
        # two warmups absorb the compile + the donated-layout second
        # compile so the captured window holds STEADY steps only
        for _ in range(2):
            state, loss = step(state, tok, tok)
        jax.block_until_ready(state)
        for i in range(3):
            with cap.step(i):
                state, loss = step(state, tok, tok)
                jax.block_until_ready(loss)
    finally:
        # a raise mid-capture must still stop the jax profiler: a
        # leaked open trace silently profiles every later leg
        cap.close()
        M.destroy_model_parallel()
    rep = monitor.analyze_trace(cap.trace_path())
    if rep.n_device_events == 0 or len(rep.steps) != 3:
        raise RuntimeError(
            f"timeline capture malformed: {rep.n_device_events} device "
            f"event(s), {len(rep.steps)} step(s) of 3")
    return {"record": rep.timeline_record(), "report": rep.to_dict()}


def _stamp_timeline(result, d):
    """Flat v11 timeline_* scalars (busy fraction, host gap,
    collective fraction, and — only where the schedule is measurable —
    the measured-overlap verdict) + the full per-step report under the
    unreserved `timeline` key."""
    result.update(d["record"])
    result["timeline"] = d["report"]


def _compile_audit_350m(on_tpu, batch, seq, cfg, master_dtype):
    """AOT compile & HBM audit of the flagship step (ISSUE 5): the
    memory/cost anatomy + the donation check + the flops cross-check
    that validates the MFU numbers derived from the flagship metric.
    master_dtype MUST be what main() passed `_fused_tokens_per_sec` —
    the audit only has value if it compiles the SAME program the
    flagship metric timed.  Runs in its OWN timed block —
    `analyze_step`'s lower().compile() does not seed the jit cache, so
    folding it into the flagship window would add a full duplicate XLA
    compile to a duration trajectory the bench keeps comparable across
    rounds."""
    from apex_tpu import monitor
    from apex_tpu.models.gpt import GPT
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4, use_pallas=on_tpu, master_dtype=master_dtype)
    opt_state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    del params
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    # lint=True: the static program passes (apex_tpu.lint, ISSUE 6)
    # run over the same traced step and attach to the report — the
    # JSON's `lint_ok` gate reads them (a flagged flagship program is
    # a correctness bug, not a perf number).  comms=True: the
    # collective inventory + overlap + ICI roofline (monitor.comms,
    # ISSUE 7) over the same compiled executable — the JSON's comms_*
    # stamps read them
    rep = monitor.analyze_step(
        step, (opt_state, tok, tok),
        analytic_flops=monitor.gpt_step_flops(cfg, batch), lint=True,
        comms=True)
    M.destroy_model_parallel()
    return rep.to_dict()


_ONLY = {
    "resnet50_img_per_sec": lambda on_tpu: round(
        _resnet50_img_per_sec(on_tpu), 1),
}


@contextlib.contextmanager
def _timed(durations, name):
    """Record a metric block's wall-clock seconds (errors included —
    a 15-minute OOM-retry spiral should be visible in the trajectory)
    into the JSON's `metric_durations_s` (ISSUE 2 satellite)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        durations[name] = round(time.perf_counter() - t0, 2)


def main():
    from apex_tpu.models.gpt import GPTConfig
    # import up front (fail FAST, not after 30 min of TPU metrics): the
    # version stamps the result JSON at the end of this function
    from apex_tpu.monitor import SCHEMA_VERSION
    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    if "--only" in sys.argv[1:]:
        if len(sys.argv) != 3 or sys.argv[1] != "--only":
            print("usage: bench.py [--only METRIC]", file=sys.stderr)
            sys.exit(2)
        metric = sys.argv[2]
        if metric not in _ONLY:
            print(f"unknown metric {metric}; choices: {sorted(_ONLY)}",
                  file=sys.stderr)
            sys.exit(2)
        if not on_tpu:
            # --only prints one device metric and nothing else: a CPU
            # number must never appear under its name
            print(f"--only {metric}: backend is "
                  f"{jax.default_backend()}, not TPU", file=sys.stderr)
            sys.exit(3)
        print(json.dumps({metric: _ONLY[metric](on_tpu)}))
        return
    if on_tpu:
        # batch 12 + bf16 Adam state (round 4): the optimizer+cast tail
        # drops from 17 ms to ~5 ms and batch 12 amortizes fixed costs
        # (b8 fp32: 46.1k, b8 bf16-state: 48.0k, b12 bf16-state: 48.7k
        # tok/s); remat=False + donate=True as before
        batch, seq = 12, 1024
        cfg = GPTConfig(vocab_size=50304, seq_len=seq, hidden=1024,
                        num_layers=24, num_heads=16, dropout=0.0,
                        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
                        remat=False, use_flash_attention=True)
    else:  # CPU smoke mode
        batch, seq = 2, 64
        cfg = GPTConfig(vocab_size=512, seq_len=seq, hidden=64,
                        num_layers=2, num_heads=4, dropout=0.0)

    durations = {}
    # ONE master-dtype decision, shared by the flagship metric and its
    # compile audit — the audit must compile the same program it audits
    master_dtype = jnp.bfloat16 if on_tpu else jnp.float32
    with _timed(durations, "gpt350m_train_tokens_per_sec_per_chip"):
        fused = _fused_tokens_per_sec(on_tpu, batch, seq, cfg,
                       master_dtype)
    result = {
        "metric": "gpt350m_train_tokens_per_sec_per_chip",
        "value": round(fused, 1),
        "unit": "tokens/s",
        "master_dtype": "bfloat16" if on_tpu else "float32",
        "vs_baseline": None,  # measured below; null = baseline didn't run
    }
    try:
        with _timed(durations, "baseline_tokens_per_sec"):
            baseline, bl_batch = _baseline_best(on_tpu, batch,
                                        seq, cfg)
        result["baseline_tokens_per_sec"] = round(baseline, 1)
        result["baseline_batch"] = bl_batch
        result["vs_baseline"] = round(fused / baseline, 2)
    except Exception as e:  # keep the primary metric even if the
        result["baseline_error"] = repr(e)[:120]  # baseline OOMs/fails
    try:
        with _timed(durations, "mha_fwd_bwd_ms"):
            mha_fused, mha_unfused = _mha_latencies(on_tpu)
        result["mha_fused_fwd_bwd_ms"] = round(mha_fused, 2)
        result["mha_unfused_fwd_bwd_ms"] = round(mha_unfused, 2)
    except Exception as e:
        result["mha_error"] = repr(e)[:120]
    try:
        with _timed(durations, "gpt1p3b_tokens_per_sec_per_chip"):
            result["gpt1p3b_tokens_per_sec_per_chip"] = round(
                _gpt1p3b_tokens_per_sec(on_tpu), 1)
    except Exception as e:
        result["gpt1p3b_error"] = repr(e)[:120]
    try:
        with _timed(durations, "bert_seq_per_sec"):
            result["bert_seq_per_sec"] = round(
                _bert_seq_per_sec(on_tpu), 1)
    except Exception as e:
        result["bert_error"] = repr(e)[:120]
    try:
        with _timed(durations, "resnet50_img_per_sec"):
            result["resnet50_img_per_sec"] = _ONLY[
                "resnet50_img_per_sec"](on_tpu)
    except Exception as e:
        result["resnet50_error"] = repr(e)[:120]
    try:
        with _timed(durations, "adam_1b_step_ms"):
            result["adam_1b_step_ms"] = round(
                _adam_1b_step_ms(on_tpu), 2)
    except Exception as e:
        result["adam_1b_error"] = repr(e)[:120]
    try:
        with _timed(durations, "zero2_n_buckets"):
            result["zero2_n_buckets"] = _zero2_bucket_sweep(on_tpu)
    except Exception as e:
        result["zero2_n_buckets_error"] = repr(e)[:120]
    # expert-parallel MoE training (ISSUE 13): dp x ep MoE-GPT
    # tokens/s under the RecompileSentry, plus the routing-health aux
    # scalars (_stamp_moe: flat moe_* v9 scalars + the dict under
    # `moe_gpt`)
    try:
        with _timed(durations, "moe_gpt"):
            moe_d = _moe_gpt_bench(on_tpu)
        _stamp_moe(result, moe_d)
    except Exception as e:
        result["moe_error"] = repr(e)[:120]
    # chunked-collective overlap (ISSUE 18): the tp=2 SP flagship step
    # timed in BOTH spellings — monolithic collectives
    # (overlap_chunks=1, byte-identical to the pre-chunking program)
    # vs the ppermute-ring chunked pipeline (overlap_chunks=2, the
    # comms/timeline probes' gpt_tp_overlap target).  (_stamp_overlap:
    # flat overlap_* scalars + the dict under `tp_overlap`.)  tp=2
    # needs two devices; with fewer the leg is not measured — never a
    # CPU stand-in under the overlap_* names
    if jax.device_count() >= 2:
        try:
            with _timed(durations, "tp_overlap"):
                ov = _overlap_measure(on_tpu)
            _stamp_overlap(result, ov)
        except Exception as e:
            result["overlap_error"] = repr(e)[:120]
    else:
        result["tp_overlap"] = "not measured"
    # serving axes (ISSUE 8): decode tokens/s + p50/p99 per-token
    # latency at N concurrent streams, and the sentry's churn verdict
    # (_stamp_serve: flat serve_* scalars + the full sweep dict)
    try:
        with _timed(durations, "serve_decode"):
            sweep = _serve_decode_bench(on_tpu)
        _stamp_serve(result, sweep)
    except Exception as e:
        result["serve_error"] = repr(e)[:120]
    # serving overload leg (ISSUE 14): the 4x storm against a bounded
    # queue — shed fraction + goodput under overload control
    # (_stamp_serve_overload: flat v10 scalars + `serving_overload`)
    try:
        with _timed(durations, "serve_overload"):
            overload = _serve_overload_bench(on_tpu)
        _stamp_serve_overload(result, overload)
    except Exception as e:
        result["serve_overload_error"] = repr(e)[:120]
    # checkpoint-cadence pricing (ISSUE 9): one async save → elastic
    # restore cycle of the ZeRO-2 flagship state, stamped as flat
    # ckpt_* v6 scalars (+ the dict under `checkpointing`)
    try:
        with _timed(durations, "ckpt_cycle"):
            cycle = _ckpt_cycle(on_tpu)
        _stamp_ckpt(result, cycle)
    except Exception as e:
        result["ckpt_error"] = repr(e)[:120]
    # fleet fault tolerance (ISSUE 11): multi-host commit barrier +
    # refusal + one orchestrated lost-rank resume, stamped as flat
    # fleet_* v8 scalars (+ the dict under `fleet`)
    try:
        with _timed(durations, "fleet_cycle"):
            fcycle = _fleet_cycle(on_tpu)
        _stamp_fleet(result, fcycle)
    except Exception as e:
        result["fleet_error"] = repr(e)[:120]
    try:
        with _timed(durations, "long_context_32k"):
            lc_ms, lc_tps = _long_context_32k(on_tpu)
        result["long_context_32k_fwd_bwd_ms"] = round(lc_ms, 1)
        result["long_context_32k_tokens_per_sec"] = round(lc_tps, 1)
    except Exception as e:
        result["long_context_error"] = repr(e)[:120]
    # runtime timeline (ISSUE 15): 3 measured steady steps of the
    # flagship program under a ProfileCapture, parsed into the flat
    # v11 timeline_* scalars (+ the per-step report dict).  Own
    # _timed key — same rule as compile_audit: capture overhead never
    # lands in a timed metric window
    try:
        with _timed(durations, "timeline"):
            tl = _timeline_anatomy(on_tpu, batch, seq, cfg,
                        master_dtype)
        _stamp_timeline(result, tl)
    except Exception as e:
        result["timeline_error"] = repr(e)[:120]
    # schema stamp + per-metric wall clock (ISSUE 2): keeps BENCH_*.json
    # trajectories comparable as metrics are added across rounds
    result["monitor_schema_version"] = SCHEMA_VERSION
    result["metric_durations_s"] = durations
    # compile & HBM observatory (ISSUE 5): the flagship step's AOT
    # memory/cost anatomy (argument/temp/alias bytes, donation check,
    # flops cross-check vs monitor.flops), per-config recompile-sentry
    # summaries, and the device-memory high-water mark after the run
    try:
        with _timed(durations, "compile_audit"):
            result["compile_audit"] = _compile_audit_350m(
                on_tpu, batch, seq, cfg, master_dtype)
    except Exception as e:
        result["compile_audit_error"] = repr(e)[:120]
    # static-lint gate (ISSUE 6): the flagship program's dtype-policy /
    # collective / donation passes, run on the exact audited step;
    # lint_ok=false means a run published numbers from a program the
    # linter would have rejected.  ok=None means the lint pass itself
    # crashed (advisory) — stamp the error, not a fake verdict.  Own
    # try so a stamp-side surprise never masquerades as an audit
    # failure (the audit dict is already in the result by now)
    try:
        lint = (result.get("compile_audit") or {}).get("lint") or {}
        if lint.get("ok") is None and lint.get("error"):
            result["lint_error"] = lint["error"][:120]
        elif lint:
            result["lint_ok"] = bool(lint.get("ok"))
        if lint.get("findings"):
            result["lint_findings"] = [
                f"{f.get('rule')} {f.get('location')}"
                for f in lint["findings"][:8]]
    except Exception as e:
        result["lint_error"] = repr(e)[:120]
    # comms observatory stamps (ISSUE 7): flat comms_* scalars from the
    # flagship audit's attached CommsReport — collective count/bytes,
    # the roofline's predicted comm seconds + fraction of step, and
    # the overlap verdict (null where unmeasurable: CPU emits no async
    # collectives; the prefix-scalar rule of SCHEMA v4 covers these).
    # Own try, like lint: a stamp-side surprise never voids the audit
    try:
        cm = (result.get("compile_audit") or {}).get("comms") or {}
        if cm.get("collectives") is None and cm.get("error"):
            result["comms_error"] = cm["error"][:120]
        elif cm:
            result["comms_n_collectives"] = int(
                sum((cm.get("counts") or {}).values()))
            result["comms_bytes"] = int(cm.get("total_comm_bytes") or 0)
            result["comms_predicted_comm_s"] = cm.get("predicted_comm_s")
            result["comms_comm_fraction"] = cm.get("comm_fraction")
            result["comms_overlap_ok"] = (
                bool(cm.get("overlap_ok"))
                if cm.get("async_supported") else None)
            ser = [c for c in cm.get("collectives", [])
                   if c.get("serialized")]
            if ser:
                # a single string scalar, not a list: the `comms_`
                # prefix is reserved for JSON scalars by SCHEMA v4
                result["comms_serialized"] = "; ".join(
                    f"{c.get('kind')} {c.get('name')} "
                    f"{c.get('operand_bytes')}B" for c in ser[:8])
    except Exception as e:
        result["comms_error"] = repr(e)[:120]
    if _SENTRY:
        result["n_compiles"] = {k: v["n_compiles"]
                                for k, v in _SENTRY.items()}
        result["recompile_sentry"] = _SENTRY
    try:
        from apex_tpu.monitor.compile import hbm_watermarks
        result["hbm"] = hbm_watermarks()
    except Exception as e:
        result["hbm_error"] = repr(e)[:120]
    # tuner cache state (ISSUE 3): which tuned configs were active and
    # how often the kernels hit them — runs with different fingerprints
    # are not comparing the same kernels
    try:
        from apex_tpu import tune
        result["tuner"] = tune.stats()
    except Exception as e:
        result["tuner_error"] = repr(e)[:120]
    print(json.dumps(result))


if __name__ == "__main__":
    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
