"""The training job of a decoder that mixes gated grouped-query
attention and Kimi Delta Attention over expert layers (Solar-Open2), as
one chip of its expert-parallel group runs it: mesh -> HybridMoE ->
FusedAdam -> init_sharded_optimizer -> make_tp_dp_train_step(donate=
True), the path `gpt_train.py` drives for `GPT` and `mla_moe_train.py`
for `MLAMoE`, with the same correctness check before it and the same
loop around it.

From the program it takes the system under test and nothing that
measures: the loop, the clock, the FLOP and byte counts
(`lib/work_hybrid_moe.py`), the peaks, the trace reduction and the
reference are all under `benchmarks/`.

The configuration file says what the chip holds (layers, experts,
vocabulary rows); a workload file's `params`:
    batch, seq          sequences a step and their length
    tensor_parallel     1: the model's parallel axis is the experts'
    sequence_parallel   false
    state_dtype         dtype of master weights and Adam moments
    lr                  Adam's step size
    recompute_mixers    optional, false: true recomputes the inner
                        activations of the two mixers in the backward
Before the step is built two checks run outside the window: the
model's per-token losses against the configuration's float32 reference,
and the chunked delta rule alone against the reference's recurrence at
the step's shape (`SCAN_TOL`).
The model is always bf16 compute and logits with fp32 router scores,
decays and delta-rule state, flash attention, fused cross entropy, no
dropout, donated state, one unpacked sequence a row.  Token ids are
uniform over the held vocabulary rows.
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
from benchmarks.lib import hlo, train_loop, work_hybrid_moe as work
from benchmarks.lib.peaks import peaks_for

# The system computes in bf16 (each rounding off by up to 2^-9 relative)
# and rounds its logits to bf16; the reference is float32 throughout and
# computes the delta rule a token at a time.  Readings of each gap, on
# the v5e at the published widths, 2 x 4096 tokens (my chip runs, PR 32):
#   the system against the reference, five seeds: rms 0.0390-0.0465; the
#     worst single token 0.258-0.298; the mean over the 8,192 tokens
#     1.8e-4-4.7e-4 (one standard error of that mean is rms / sqrt(tokens)
#     = 5e-4);
#   the reference with every GEMM's operands rounded to float8_e5m2, the
#     nearest precision below bf16 that keeps its range, against itself
#     in float32 (two seeds): rms 1.13-1.15, worst token 4.2-4.5, mean
#     0.015-0.025;
#   the reference with bf16 operands: rms 0.035-0.037, worst token
#     0.26-0.30: the system is what bf16 gives.
# Each bound lies between its two readings: the rms at 2.6 times the
# largest the system gave and a ninth of the fp8 reading; the single
# token at 3 times the largest seen (a maximum over 8,192 tokens of a
# heavy tail reads higher on fresh seeds: a near-tie among a router's 320
# scores that bf16 settles the other way swaps an expert) and under a
# quarter of the fp8 reading's smallest; the mean at 7 standard errors, 7
# times the largest seen and a quarter of the fp8 reading's smallest.
# Over twenty-one seeds in all the worst token read up to 0.446, half
# its bound, the rms up to 0.0478 and the mean up to 1.0e-3.
# What these three cannot see: the reference with the delta rule's
# state rounded to bf16 after every token (GEMMs in float32) reads rms
# 0.016, worst token 0.19, mean 1.5e-4, all under what bf16 GEMMs alone
# give.  SCAN_TOL below is the bound such a scan fails.
RMS_TOL = 0.12
TOKEN_TOL = 0.9
MEAN_TOL = 3.5e-3

# The delta rule alone, at the step's own shape (`_scan_agrees_with_
# recurrence`): the program's chunked op against the reference's
# recurrence on the same q, k, v, g, beta, which the first KDA layer's
# own weights make from seeded unit-rms activations, with the decay
# pinned at the slow end of what the configuration's initialisation
# draws from (`assumed.kda_init`: A_h = log 1, time step SLOW_STEP).  A
# state there lives about a thousand tokens, which is where a rounding
# of it adds up; at the decays as drawn most channels forget inside a
# chunk, and a state rounded to bf16 reads 1.4 to 1.9 times the op's
# own gap (0.0049-0.0078 against 0.0036-0.0042, four heads on the CPU),
# too near for a bound.  The gap is the rms of the difference over the
# rms of the recurrence's output.  Readings on the v5e at 1 x 64 heads
# x 4096, five seeds (my chip run, PR 32): the op 0.006289-0.006300
# (bf16 operands in every product, the state's among them, and a bf16
# output: rounding the recurrence's own output to bf16 reads 0.0017);
# the recurrence with its state rounded to bf16 after every token
# 0.018985-0.019023, to float16 0.00224.  The bound is 1.75 times the
# op's largest reading and 0.58 of the bf16 state's smallest.
SLOW_STEP = 1e-3
SCAN_TOL = 0.011


def model_config(config: dict, **overrides):
    """The program's HybridMoEConfig for a configuration file."""
    from apex_tpu.models.hybrid_moe import HybridMoEConfig

    s = work.sizes(config)
    return HybridMoEConfig(
        vocab_size=s["vocab"], hidden=s["hidden"], num_layers=s["layers"],
        attention_layers=s["attends"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        kda_heads=s["kda_heads"], kda_head_dim=s["kda_dim"],
        conv_kernel=s["taps"], kda_rank=s["kda_rank"],
        allow_neg_eigval=bool(config["kda_allow_neg_eigval"]),
        moe_intermediate_size=s["expert_ffn"],
        n_routed_experts=s["published"], num_experts_per_tok=s["top_k"],
        n_shared_experts=s["shared"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=config["norm_topk_prob"],
        rms_norm_eps=config["rms_norm_eps"],
        experts_first=config.get("experts_first", 0),
        experts_count=s["held"],
        expert_rows_factor=float(config.get("expert_rows_factor", 2.0)),
        init_std=config.get("initializer_range", 0.02), **overrides)


def run(spec) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import tune
    from apex_tpu.models.hybrid_moe import HybridMoE
    from apex_tpu.monitor.compile import RecompileSentry
    from apex_tpu.ops import delta_rule
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
                       logits_dtype=jnp.bfloat16,
                       recompute_mixers=bool(p.get("recompute_mixers")))

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=1,
                                       devices=devices)
    model = HybridMoE(cfg)
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
    agrees = _scan_agrees_with_recurrence(spec, model, params, batch,
                                          seq) and agrees
    gc.collect()

    # ---- the step --------------------------------------------------------
    opt = FusedAdam(lr=p["lr"], master_dtype=state_dtype)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    del params            # the donated state owns the only copy from here
    n_local = int(state.params.shape[0])       # flat elements on the device
    jax.block_until_ready(state)
    spec.emit(phase="state")

    delta_rule.reset_stats()    # what the step's own trace counts
    t0 = time.perf_counter()
    lowered = step.lower(state, *batches[0])
    t1 = time.perf_counter()
    scan = delta_rule.stats()
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
              delta_rule=scan,
              argument_bytes=int(memory.argument_size_in_bytes),
              temp_bytes=int(memory.temp_size_in_bytes),
              generated_code_bytes=int(memory.generated_code_size_in_bytes))
    del lowered, compiled, text
    if not spec.rehearse and not (kernels["flash"] and kernels["adam"]):
        raise RuntimeError(
            f"flash kernels {len(kernels['flash'])}, Adam kernels "
            f"{len(kernels['adam'])} among {len(calls)} tpu_custom_call(s): "
            "an op silently took its jnp reference instead of its kernel")
    if scan["calls"] != sizes["kda"]:
        raise RuntimeError(
            f"{scan['calls']} delta-rule calls traced for "
            f"{sizes['kda']} KDA layers")

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
              moe_counts=moe_counts, moe_overflow=moe_overflow)
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
                     "moe_counts": moe_counts, "moe_overflow": moe_overflow,
                     "kda_saved_state_bytes": scan["saved_state_bytes"],
                     "kda_chunk": scan["chunk"]},
        "tokens_per_s": tokens_per_s,
        "chips": 1,
        "peak_bytes": [peak],
        "kernels": kernels,
        "work": {
            "flops_per_token": work.train_flops_per_token(sizes, seq),
            # per step: the attention of every layer that attends (64
            # query heads' pairs, 8 kv heads' bytes), the delta rule of
            # every KDA layer, the grouped GEMMs of every expert layer,
            # one pass over the flat state
            "flash": {k: v * sizes["attention"]
                      for k, v in work.flash_attention_work(
                          batch, cfg.num_heads, cfg.num_kv_heads, seq,
                          cfg.head_dim).items()},
            "scan": work.scan_work(sizes, batch, seq),
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


def scan_gap(model, params, reference, batch, seq, seed, device,
             state_dtype=None) -> float:
    """The rms of (the program's `gated_delta_rule` - the reference's
    recurrence) over the rms of the recurrence's output, at (batch, the
    model's heads, seq): both read the q, k, v, g, beta that the first
    KDA layer's weights make of seeded unit-rms activations under the
    slowest decay the initialisation draws from.  With `state_dtype`,
    what the reference's own recurrence reads against itself when it
    rounds its state to that dtype after every token: the control the
    bound is set under."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.delta_rule import gated_delta_rule

    c = model.c
    layer = min(i for i in range(c.num_layers)
                if i not in c.attention_layers)
    slow = SLOW_STEP + math.log(-math.expm1(-SLOW_STEP))  # softplus^-1

    def inputs(attn, key):
        x = jax.random.normal(key, (batch, seq, c.hidden), jnp.float32)
        a = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))
        pinned = dict(attn, a_log=jnp.zeros_like(attn["a_log"]),
                      dt_bias=jnp.full_like(attn["dt_bias"], slow))
        return model.scan_inputs(pinned, a.astype(c.dtype))

    args = jax.jit(inputs)(params[f"block{layer}"]["attn"],
                           jax.random.PRNGKey(seed))
    want = np.asarray(reference.scan_outputs(*args, device=device))
    if state_dtype is None:
        # as the model calls it: the chunk and the heads a pass come
        # from the same place as the step's
        got = jax.jit(lambda *x: gated_delta_rule(*x, chunk=c.scan_chunk))(
            *args)
    else:
        got = reference.scan_outputs(*args, device=device,
                                     state_dtype=state_dtype)
    got = np.asarray(got, np.float32)
    return float(np.sqrt(np.mean(np.square(got - want))
                         / np.mean(np.square(want))))


def _scan_agrees_with_recurrence(spec, model, params, batch, seq) -> bool:
    """Correctness of the delta rule's precision, outside the window:
    the op as the step runs it (bf16 operands, the tuned chunk, the
    state in float32) at the step's shape against the reference's
    float32 recurrence, a token at a time."""
    reference = spec.load("reference", spec.config["reference"])
    gap = scan_gap(model, params, reference, batch, seq, spec.seed + 2,
                   spec.devices[0])
    agrees = bool(gap <= SCAN_TOL)       # False for a nan
    spec.emit(phase="scan_check", gap=gap, tol=SCAN_TOL, agrees=agrees,
              slow_step=SLOW_STEP)
    return agrees


def _agrees_with_reference(spec, model, mesh, params, tokens, labels) -> bool:
    """Correctness, outside the window: the system's own per-token
    losses (bf16, the grouped-query flash kernel, the chunked delta
    rule, the grouped GEMMs, the fused cross entropy) on a seeded
    sample of sequences of the cell's length, against the
    configuration's plain float32 reference (the delta rule a token at
    a time) on the same weights and the same share."""
    import jax
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.mesh import DP_AXIS

    system = jax.jit(shard_map(
        lambda prm, tok, lab: model.token_losses(prm, tok, lab)[0],
        mesh=mesh,
        in_specs=(model.partition_specs(), P(DP_AXIS), P(DP_AXIS)),
        out_specs=P(DP_AXIS), check_vma=False))
    # a sequence at a time, the step's own shape: the kernels take the
    # shapes (and the tuned configurations) the window will use
    got = np.concatenate([
        np.asarray(system(params, tokens[i:i + 1], labels[i:i + 1]),
                   np.float32) for i in range(tokens.shape[0])])
    spec.emit(phase="system_forward")
    reference = spec.load("reference", spec.config["reference"])
    want = np.asarray(reference.token_losses(
        params, tokens, labels, arch=spec.config,
        device=spec.devices[0])[0], np.float32)
    rms_gap = float(np.sqrt(np.mean(np.square(got - want))))
    token_gap = float(np.max(np.abs(got - want)))
    mean_gap = float(abs(got.mean(dtype=np.float64)
                         - want.mean(dtype=np.float64)))
    agrees = bool(np.isfinite(got).all() and rms_gap <= RMS_TOL
                  and token_gap <= TOKEN_TOL and mean_gap <= MEAN_TOL)
    spec.emit(phase="reference", system_mean=float(got.mean()),
              reference_mean=float(want.mean()), mean_gap=mean_gap,
              rms_gap=rms_gap, token_gap=token_gap, mean_tol=MEAN_TOL,
              rms_tol=RMS_TOL, token_tol=TOKEN_TOL, agrees=agrees,
              tokens=int(got.size))
    return agrees
