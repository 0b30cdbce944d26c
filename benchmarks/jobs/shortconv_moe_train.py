"""The training job of a decoder of gated short convolutions and
QK-normed rotary grouped-query attention over expert layers without a
shared expert (LFM2-MoE), as one chip of its expert-parallel pair runs
it: mesh -> ShortConvMoE -> FusedAdam -> init_sharded_optimizer ->
make_tp_dp_train_step(donate=True), the path `gpt_train.py` drives for
`GPT` and `hybrid_moe_train.py` for `HybridMoE`, with the same loop
around it.

From the program it takes the system under test and nothing that
measures: the loop, the clock, the FLOP and byte counts
(`lib/work_shortconv_moe.py`), the peaks, the trace reduction and the
reference are all under `benchmarks/`.

The configuration file says what the chip holds (layers and their
kinds, experts, vocabulary rows, whether the head is the embedding); a
workload file's `params`:
    batch, seq          rows a step and their length
    tensor_parallel     1: the model's parallel axis is the experts'
    sequence_parallel   false
    state_dtype         dtype of master weights and Adam moments
    lr                  Adam's step size
Token ids are uniform over the held vocabulary rows, one document a
row, labels the row rolled by one; ring, check rows and weights all
from `--seed`.

Before the step is built three checks run outside the window: the
model's per-token losses against the configuration's float32 reference
on two seeded rows of the cell's length; the same against the
reference with every convolution's three taps reversed in time, which
has to fail (or the first check could not see a convolution that reads
the wrong tap for the current token); and the router's gate alone
against the reference's float32 scoring, which a router that scored in
a lower precision fails where the losses do not show it.  The model is
always bf16 compute and logits with fp32 router scores and convolution
sums, flash attention, fused cross entropy, no dropout, no recompute,
donated state.
"""

from __future__ import annotations

import gc
import math
import time

from benchmarks.jobs.gpt_train import (
    CHECK_SEQUENCES,
    LOSS_AT,
    RING,
    WARMUP_STEPS,
    _peak_bytes,
    _traced_steps,
)
from benchmarks.jobs.kimi_linear_train import gaps, system_losses
from benchmarks.lib import hlo, train_loop, work_shortconv_moe as work
from benchmarks.lib.peaks import peaks_for

# The system computes in bf16 (each rounding off by up to 2^-9 relative)
# and rounds its logits to bf16; the reference is float32 throughout.
# Readings of each gap, on the v5e at the published widths, 2 rows of
# 8,192 tokens (my chip runs, PR 39):
#   the system against the reference, ten seeds: rms 0.0340-0.0357; the
#     worst single token 0.328-0.602; the mean over the 16,384 tokens
#     2.6e-5-7.6e-4 (one standard error of that mean is rms / sqrt(
#     tokens) = 2.8e-4);
#   the reference with every GEMM's operands rounded to bfloat16,
#     against itself in float32 (nine seeds): rms 0.0290-0.0308, worst
#     token 0.338-0.515: the system is what bf16 gives, with its
#     activations and logits rounded besides;
#   the reference with every GEMM's operands rounded to float8_e5m2, the
#     nearest precision below bf16 that keeps its range (nine seeds): rms
#     0.407-0.416, worst token 1.71-1.97, mean 7.6e-3-1.5e-2;
#   the taps control, the system against the reference with every
#     convolution's taps reversed in time (ten seeds): rms 1.22-1.23,
#     worst token 4.7-5.8, mean 3.4e-3-2.2e-2.
# Each bound lies between its readings: the rms at 2.8 times the largest
# the system gave, a quarter of the fp8 reading's smallest and a
# twelfth of the control's; the single token at 1.8 times the largest
# seen (a maximum over 16,384 tokens of a heavy tail reads higher on
# fresh seeds: a near-tie among a router's 32 scores that bf16 settles
# the other way swaps one of four experts), 0.64 of the fp8 reading's
# smallest and under a quarter of the control's; the mean at 3.3 times
# the largest seen, 9 standard errors, and a third of the fp8
# reading's smallest.  The fp8 reading fails all three on every seed,
# the taps control the rms and the single token on every seed: a
# convolution that reads the wrong tap is seen.  (Ten later seeds read
# inside these ranges, but for an rms down to 0.0326, a worst token
# down to 0.303 and a control's worst token up to 6.0.)
# What these three cannot see: the reference with its router's scores
# rounded to bf16 (GEMMs in float32) reads rms 0.0255-0.0268, worst
# token 0.370-0.526, as little as bf16 GEMMs alone.  The router check
# below is the bound such a router fails.
RMS_TOL = 0.10
TOKEN_TOL = 1.1
MEAN_TOL = 2.5e-3

# The gate alone at the step's own row count (`router_gap`): the
# program's `sigmoid_topk_gates` against the reference's float32 scoring
# on 8,192 seeded unit-rms rows in bf16 under the first expert layer's
# router.  Readings on the v5e, six seeds (my chip run, PR 39): the
# program 0 rows of 8,192 with another choice and a weight gap of 0.0
# (bf16 operands' products are exact in float32; the order of the 2,048
# sums may differ and did not); the reference with its scores rounded to
# float16, the nearest precision below float32, 0.22-0.42% of the rows
# and 1.13e-4-1.25e-4; to bfloat16 2.0-2.5% and 9.2e-4-1.02e-3.  The
# bounds: 8 rows of 8,192 (a near-tie inside float32's own rounding is
# one row in some runs), under half the float16 reading's smallest and a
# twentieth of bfloat16's; a weight gap of 3e-5, a thousand roundings of
# a float32 weight near 1/4 and a quarter of the float16 reading's
# smallest.
ROUTER_FLIPPED_TOL = 1e-3
ROUTER_WEIGHT_TOL = 3e-5


def model_config(config: dict, **overrides):
    """The program's ShortConvMoEConfig for a configuration file."""
    from apex_tpu.models.shortconv_moe import ShortConvMoEConfig

    s = work.sizes(config)
    return ShortConvMoEConfig(
        vocab_size=s["vocab"], hidden=s["hidden"], num_layers=s["layers"],
        layer_types=s["kinds"], conv_kernel=s["taps"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        rope_theta=float(config["rope_theta"]),
        num_dense_layers=s["dense"], intermediate_size=s["ffn"],
        moe_intermediate_size=s["expert_ffn"],
        n_routed_experts=s["published"], num_experts_per_tok=s["top_k"],
        n_shared_experts=0,
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        rms_norm_eps=config["norm_eps"],
        experts_first=config.get("experts_first", 0),
        experts_count=s["held"], tie_word_embeddings=s["tied"],
        init_std=config.get("initializer_range", 0.02), **overrides)


def within(read: dict) -> bool:
    return bool(read["rms_gap"] <= RMS_TOL and read["token_gap"] <= TOKEN_TOL
                and read["mean_gap"] <= MEAN_TOL)


def run(spec) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import tune
    from apex_tpu.models.shortconv_moe import ShortConvMoE
    from apex_tpu.monitor.compile import RecompileSentry
    from apex_tpu.optimizers import FusedAdam, flat as F
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    p = spec.workload["params"]
    batch, seq = p["batch"], p["seq"]
    sizes = work.sizes(spec.config)
    devices = list(spec.devices)
    if p["tensor_parallel"] != 1 or p["sequence_parallel"] or len(devices) != 1:
        raise ValueError("this job runs one chip's share of an expert-"
                         "parallel group: tensor_parallel 1, one device")
    if seq > sizes["positions"]:
        raise ValueError(f"seq {seq} is beyond the configuration's "
                         f"{sizes['positions']} positions")
    state_dtype = jnp.dtype(p["state_dtype"])
    cfg = model_config(spec.config, dtype=jnp.bfloat16,
                       logits_dtype=jnp.bfloat16)

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=1,
                                       devices=devices)
    model = ShortConvMoE(cfg)
    specs = model.partition_specs()
    on_mesh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                           is_leaf=lambda s: isinstance(s, P))

    # the weights: one jitted call from the seed, born on the device
    params = jax.jit(model.init, out_shardings=on_mesh)(
        jax.random.PRNGKey(spec.seed))
    spec.emit(phase="weights")

    # the batches: a ring on the device, and the correctness sample
    def make_tokens(key):
        k1, k2 = jax.random.split(key)
        ring = jax.random.randint(k1, (RING, batch, seq), 0, cfg.vocab_size)
        sample = jax.random.randint(k2, (CHECK_SEQUENCES, seq), 0,
                                    cfg.vocab_size)
        return (ring, jnp.roll(ring, -1, axis=2),
                sample, jnp.roll(sample, -1, axis=1))

    by_dp = NamedSharding(mesh, P(M.DP_AXIS))
    ring_dp = NamedSharding(mesh, P(None, M.DP_AXIS))
    ring, ring_labels, sample, sample_labels = jax.jit(
        make_tokens, out_shardings=(ring_dp, ring_dp, by_dp, by_dp))(
        jax.random.PRNGKey(spec.seed + 1))
    batches = [(jax.device_put(ring[i], by_dp),
                jax.device_put(ring_labels[i], by_dp)) for i in range(RING)]
    del ring, ring_labels

    agrees = _agrees_with_reference(spec, model, mesh, params, sample,
                                    sample_labels)
    del sample, sample_labels
    agrees = _router_agrees_with_reference(spec, model, params,
                                           batch * seq) and agrees
    gc.collect()

    # ---- the step --------------------------------------------------------
    opt = FusedAdam(lr=p["lr"], master_dtype=state_dtype)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    del params            # the donated state owns the only copy from here
    n_local = int(state.params.shape[0])       # flat elements on the device
    jax.block_until_ready(state)
    spec.emit(phase="state")

    tune.reset_stats()      # what the step's own trace looks up
    t0 = time.perf_counter()
    lowered = step.lower(state, *batches[0])
    t1 = time.perf_counter()
    compiled = lowered.compile()    # XLA, or a read of the compile cache
    t2 = time.perf_counter()
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    calls = hlo.custom_calls(text)
    # the program names its kernels (monitor.scopes.KERNELS)
    kernels = {
        "flash": [n for n, _ in calls
                  if n.startswith(("flash_fwd", "flash_bwd"))],
        "adam": [n for n, _ in calls if n.startswith("adam_flat")]}
    spec.emit(phase="compile", trace_lower_s=t1 - t0, compile_s=t2 - t1,
              tpu_custom_calls=len(calls),
              flash_kernels=len(kernels["flash"]),
              adam_kernels=len(kernels["adam"]),
              grouped_gemm_kernels=sum(
                  n.startswith("ragged-dot") for n, _ in calls),
              argument_bytes=int(memory.argument_size_in_bytes),
              temp_bytes=int(memory.temp_size_in_bytes),
              generated_code_bytes=int(memory.generated_code_size_in_bytes))
    del lowered, compiled, text
    if not spec.rehearse and not (kernels["flash"] and kernels["adam"]):
        raise RuntimeError(
            f"flash kernels {len(kernels['flash'])}, Adam kernels "
            f"{len(kernels['adam'])} among {len(calls)} tpu_custom_call(s): "
            "an op silently took its jnp reference instead of its kernel")

    sentry = RecompileSentry(step, name=spec.name, warn=False)
    log = train_loop.StepLog()
    state = train_loop.run(sentry, state, batches, log, steps=WARMUP_STEPS)
    sentry.mark_steady()

    # ---- the window ------------------------------------------------------
    window_started = time.perf_counter()
    xplane = None
    if spec.trace:
        # the rate from an untraced half window, then the profiler on
        # over a few steps of their own: traces are large
        state = train_loop.run(sentry, state, batches, log,
                               seconds=spec.seconds / 2)
        state, xplane = _traced_steps(spec, sentry, state, batches, log)
    else:
        state = train_loop.run(sentry, state, batches, log,
                               seconds=spec.seconds)
    tokens_per_s = train_loop.rate_per_s(log, 1, batch * seq)

    # ---- after -----------------------------------------------------------
    losses = log.losses
    finite = [math.isfinite(v) for v in losses]
    falling = (len(losses) >= 16
               and sum(losses[-8:]) / 8 < sum(losses[:8]) / 8)
    stats = devices[0].memory_stats() or {}
    peak = _peak_bytes(stats)

    # the routers' counters, from a forward of the last batch on the
    # weights the window left: outside the window, after the memory read
    def counts(flat, tokens):
        return model.routing_counts(F.unflatten(flat, opt.spec), tokens)

    last = batches[(len(losses) - 1) % RING]
    moe_counts, moe_overflow = jax.device_get(jax.jit(shard_map(
        counts, mesh=mesh, in_specs=(P(("pp", "tp")), P(M.DP_AXIS)),
        out_specs=(P(), P()), check_vma=False))(state.params, last[0]))
    moe_counts, moe_overflow = moe_counts.tolist(), moe_overflow.tolist()
    no_overflow = sum(moe_overflow) == 0

    first, last_step = log.segments[1]
    step_s = sorted(b - a for a, b in zip(
        log.completed_at[first:last_step],
        log.completed_at[first + 1:]))
    spec.emit(phase="window", losses=losses,
              step_s_p50=step_s[len(step_s) // 2],
              step_s_p90=step_s[len(step_s) * 9 // 10],
              step_s_max=step_s[-1], sentry=sentry.summary(),
              tune=tune.stats(), peak_bytes=[peak], memory_stats=stats,
              moe_counts=moe_counts, moe_overflow=moe_overflow,
              held_rows_per_step=[sum(row) for row in moe_counts])
    correct = bool(agrees and all(finite) and falling and no_overflow
                   and sentry.steady_recompiles == 0)
    if not correct:
        spec.emit(phase="incorrect", agrees=agrees, finite=all(finite),
                  falling=falling, moe_overflow=moe_overflow,
                  steady_recompiles=sentry.steady_recompiles)

    end_to_end = {"train_tokens_per_s": tokens_per_s,
                  "setup_s": window_started - spec.t0}
    if len(losses) >= LOSS_AT[1]:
        end_to_end["loss_after_16_steps"] = (
            sum(losses[slice(*LOSS_AT)]) / (LOSS_AT[1] - LOSS_AT[0]))
    observed = {
        "spans": {"trace_lower_s": t1 - t0, "compile_s": t2 - t1,
                  "dispatch_s": log.dispatch_s[WARMUP_STEPS:]},
        "counters": {"steady_recompiles": sentry.steady_recompiles,
                     "moe_counts": moe_counts, "moe_overflow": moe_overflow},
        "tokens_per_s": tokens_per_s,
        "chips": 1,
        "peak_bytes": [peak],
        "kernels": kernels,
        "work": {
            "flops_per_token": work.train_flops_per_token(sizes, seq),
            # per step: the attention of every layer that attends (32
            # query heads' pairs, 8 kv heads' bytes), the gates and taps
            # of every convolutional mixer, the grouped GEMMs of every
            # expert layer, one pass over the flat state
            "flash": work.flash_attention_work(sizes, batch, seq),
            "short_conv": work.short_conv_work(sizes, batch * seq),
            "expert_gemm": work.expert_gemm_work(sizes, batch * seq),
            "adam_bytes": work.adam_bytes(n_local, state_dtype.itemsize,
                                          jnp.dtype(cfg.dtype).itemsize)},
        "peaks": (None if spec.rehearse
                  else peaks_for(devices[0].device_kind)),
        "xplane": xplane,
    }
    return {"correct": correct, "attempted": len(losses) - WARMUP_STEPS,
            "failed": sum(not ok for ok in finite[WARMUP_STEPS:]),
            "end_to_end": end_to_end, "observed": observed,
            "memory_peak_bytes": peak}


def _agrees_with_reference(spec, model, mesh, params, tokens, labels) -> bool:
    """Correctness, outside the window: the system's own per-token
    losses (bf16, the compiled short convolution, q and k normed and
    turned, the grouped-query flash kernels, the grouped GEMMs, the
    tied head under the fused cross entropy) on two seeded rows of the
    cell's length, against the configuration's plain float32 reference
    on the same weights and the same share; and the control: against
    the same reference with its convolutions' taps reversed in time the
    system has to read outside the bounds."""
    import numpy as np

    got = system_losses(model, mesh, params, tokens, labels)
    spec.emit(phase="system_forward")
    reference = spec.load("reference", spec.config["reference"])

    def reference_losses(**kw):
        return np.asarray(reference.token_losses(
            params, tokens, labels, arch=spec.config,
            device=spec.devices[0], **kw)[0], np.float32)

    want = reference_losses()
    read = gaps(got, want)
    turned = gaps(got, reference_losses(taps_reversed=True))
    agrees = bool(np.isfinite(got).all() and within(read))
    taps_seen = not within(turned)
    spec.emit(phase="reference", system_mean=float(got.mean()),
              reference_mean=float(want.mean()), **read, mean_tol=MEAN_TOL,
              rms_tol=RMS_TOL, token_tol=TOKEN_TOL, agrees=agrees,
              tokens=int(got.size))
    spec.emit(phase="taps_control", **turned, fails_as_it_must=taps_seen)
    return agrees and taps_seen


def router_gap(model, params, reference, arch, tokens: int, seed: int,
               device, router_dtype=None) -> dict:
    """The program's gate (`moe.sigmoid_topk_gates`, as the expert
    layer calls it) against the reference's float32 scoring, on
    `tokens` seeded unit-rms rows in the model's dtype under the first
    expert layer's own router: `flipped`, the share of the rows whose
    chosen experts differ, and `weight_gap`, the largest difference of
    a weight over the rows whose choice agrees.  With `router_dtype`,
    what the reference reads against itself when it rounds its scores
    to that dtype: the control the bounds are set under."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.moe import sigmoid_topk_gates

    c = model.c
    mlp = params[f"block{c.num_dense_layers}"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, c.hidden),
                          jnp.float32)
    m = (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))
         ).astype(c.dtype)
    want = np.asarray(reference.router_weights(mlp, m, arch=arch,
                                               device=device))
    if router_dtype is None:
        def dense(m, router, bias):
            gates = sigmoid_topk_gates(
                m, router, bias, c.num_experts_per_tok,
                scale=c.routed_scaling_factor, renormalize=c.norm_topk_prob)
            return jnp.sum(jax.nn.one_hot(gates.idx, c.n_routed_experts)
                           * gates.weight[..., None], axis=1)

        got = np.asarray(jax.jit(dense)(m, mlp["router"], mlp["router_bias"]))
    else:
        got = np.asarray(reference.router_weights(
            mlp, m, arch=arch, device=device, router_dtype=router_dtype))
    same = ((got > 0) == (want > 0)).all(axis=-1)
    return {"flipped": float(1.0 - same.mean()),
            "weight_gap": float(np.abs(got - want)[same].max())}


def _router_agrees_with_reference(spec, model, params, tokens: int) -> bool:
    """Correctness of the router's precision, outside the window: the
    per-token losses cannot see a router that scores in bf16 (the
    readings beside the bounds), so the gate alone is held to the
    reference's float32 scoring at the step's own row count."""
    reference = spec.load("reference", spec.config["reference"])
    read = router_gap(model, params, reference, spec.config, tokens,
                      spec.seed + 2, spec.devices[0])
    agrees = bool(read["flipped"] <= ROUTER_FLIPPED_TOL
                  and read["weight_gap"] <= ROUTER_WEIGHT_TOL)
    spec.emit(phase="router_check", **read, flipped_tol=ROUTER_FLIPPED_TOL,
              weight_tol=ROUTER_WEIGHT_TOL, agrees=agrees)
    return agrees
