"""The training job of a latent-attention, sparse-expert model of the
DeepSeek-V3 family, as one chip of its expert-parallel group runs it:
mesh -> MLAMoE -> FusedAdam -> init_sharded_optimizer ->
make_tp_dp_train_step(donate=True), the path `gpt_train.py` drives for
`GPT`, with the same correctness check before it and the same loop
around it.

From the program it takes the system under test and nothing that
measures: the loop, the clock, the FLOP and byte counts
(`lib/work_mla_moe.py`), the peaks, the trace reduction and the
reference are all under `benchmarks/`.

The configuration file says what the chip holds (layers, experts,
vocabulary rows); a workload file's `params`:
    batch, seq          sequences a step and their length
    tensor_parallel     1: the model's parallel axis is the experts'
    sequence_parallel   false
    state_dtype         dtype of master weights and Adam moments
    lr                  Adam's step size
The model is always bf16 compute and logits with fp32 router scores,
flash attention, fused cross entropy, no dropout, no recompute, donated
state.  Token ids are uniform over the held vocabulary rows.
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
from benchmarks.lib import hlo, train_loop, work_mla_moe as work
from benchmarks.lib.peaks import peaks_for

# The system computes in bf16 (each rounding off by up to 2^-9 relative)
# and rounds its logits to bf16; the reference is float32 throughout.
# Two readings of each gap, on the v5e at the published widths, 2 x 4096
# tokens, five seeds (my chip runs, PR 28), main head / MTP head:
#   the system against the reference: rms 0.0145-0.0178 / 0.0118-0.0153;
#     the worst single token 0.23-0.41 / 0.17-0.31 (some twenty standard
#     deviations: a near-tie among a router's 256 scores that bf16
#     activations settle the other way swaps one of a token's eight
#     experts, a discrete change no rounding bound covers); the mean over
#     the 8,192 tokens 7e-6-2.4e-4 / 9e-5-1.9e-4 (one standard error of
#     that mean is rms / sqrt(tokens) = 2e-4);
#   the reference with every GEMM's operands rounded to float8_e5m2, the
#     nearest precision below bf16 that keeps its range (unscaled
#     float8_e4m3 underflows on std-0.02 weights and reads 2.6 times
#     worse), against itself in float32: rms 0.28-0.30 / 0.23-0.24, worst
#     token 0.99-1.27 / 0.79-0.96, mean 7e-3-1.2e-2 / 7e-3-1.1e-2.
# Each bound lies between its two readings: the rms at 3.4 times the
# largest the system gave and a fifth of the fp8 reading (an fp8 or int8
# GEMM, or a scaled fp8 at half the e5m2 error, fails it with room); the
# mean at 7 standard errors, 6 times the largest seen and a fifth of the
# fp8 reading; the single token at 1.7 times the largest seen, with the
# more room on the system's side because a maximum over 8,192 tokens of
# a heavy tail reads higher on fresh seeds, and still under the fp8
# reading's smallest.  The reference with bf16 operands reads rms
# 0.013-0.015, worst token 0.21-0.32: the system is what bf16 gives.
RMS_TOL = 0.06
TOKEN_TOL = 0.7
MEAN_TOL = 1.5e-3


def model_config(config: dict, **overrides):
    """The program's MLAMoEConfig for a configuration file."""
    from apex_tpu.models.mla_moe import MLAMoEConfig

    s = work.sizes(config)
    return MLAMoEConfig(
        vocab_size=s["vocab"], hidden=s["hidden"], num_heads=s["heads"],
        q_lora_rank=s["q_rank"], kv_lora_rank=s["kv_rank"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
        v_head_dim=s["v"], intermediate_size=s["ffn"],
        moe_intermediate_size=s["expert_ffn"],
        n_routed_experts=s["published"], num_experts_per_tok=s["top_k"],
        n_shared_experts=s["shared"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        rope_theta=config["rope_theta"], rms_norm_eps=config["rms_norm_eps"],
        first_k_dense_replace=s["dense"],
        num_expert_layers=s["layers"] - s["dense"], mtp=s["mtp"] > 0,
        experts_first=config.get("experts_first", 0),
        experts_count=s["held"],
        mtp_lambda=config.get("mtp_loss_weight", 0.3),
        init_std=config.get("initializer_range", 0.02), **overrides)


def run(spec) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import tune
    from apex_tpu.models.mla_moe import MLAMoE
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
    model = MLAMoE(cfg)
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
    gc.collect()

    # ---- the step --------------------------------------------------------
    opt = FusedAdam(lr=p["lr"], master_dtype=state_dtype)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    del params            # the donated state owns the only copy from here
    n_local = int(state.params.shape[0])       # flat elements on the device
    jax.block_until_ready(state)
    spec.emit(phase="state")

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
    def counts(flat, tokens, labels):
        return model.routing_counts(F.unflatten(flat, opt.spec), tokens,
                                    labels)

    last = batches[(len(losses) - 1) % RING]
    moe_counts, moe_overflow = jax.device_get(jax.jit(shard_map(
        counts, mesh=mesh, in_specs=(P(("pp", "tp")), P(M.DP_AXIS),
                                     P(M.DP_AXIS)),
        out_specs=(P(), P()), check_vma=False))(state.params, *last))
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
    n_blocks = cfg.num_layers + int(cfg.mtp)
    observed = {
        "spans": {"trace_lower_s": t1 - t0, "compile_s": t2 - t1,
                  "dispatch_s": log.dispatch_s[WARMUP_STEPS:]},
        "counters": {"steady_recompiles": sentry.steady_recompiles,
                     "moe_counts": moe_counts, "moe_overflow": moe_overflow,
                     "mtp_block": cfg.num_layers if cfg.mtp else None},
        "tokens_per_s": tokens_per_s,
        "chips": 1,
        "peak_bytes": [peak],
        "kernels": kernels,
        "work": {
            "flops_per_token": work.train_flops_per_token(sizes, seq),
            # per step: every block's attention, the grouped GEMMs of
            # every expert layer, one pass over the flat state
            "flash": {k: v * n_blocks for k, v in work.flash_attention_work(
                batch, cfg.num_heads, seq, cfg.qk_head_dim,
                cfg.v_head_dim).items()},
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
    losses (bf16, the flash kernel at 192/128, the grouped GEMMs, the
    fused cross entropy) of the main head and of the MTP head on a
    seeded sample of sequences of the cell's length, against the
    configuration's plain float32 reference on the same weights and the
    same share."""
    import jax
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.mesh import DP_AXIS

    def token_losses(prm, tok, lab):
        main, mtp, _ = model.token_losses(prm, tok, lab)
        return main, mtp

    system = jax.jit(shard_map(
        token_losses, mesh=mesh,
        in_specs=(model.partition_specs(), P(DP_AXIS), P(DP_AXIS)),
        out_specs=(P(DP_AXIS), P(DP_AXIS)), check_vma=False))
    got = [np.asarray(a, np.float32) for a in system(params, tokens, labels)]
    spec.emit(phase="system_forward")
    reference = spec.load("reference", spec.config["reference"])
    want = [np.asarray(a, np.float32) for a in reference.token_losses(
        params, tokens, labels, arch=spec.config, device=spec.devices[0])]
    record, agrees = {}, True
    for head, g, w in zip(("main", "mtp"), got, want):
        rms_gap = float(np.sqrt(np.mean(np.square(g - w))))
        token_gap = float(np.max(np.abs(g - w)))
        mean_gap = float(abs(g.mean(dtype=np.float64)
                             - w.mean(dtype=np.float64)))
        ok = bool(np.isfinite(g).all() and rms_gap <= RMS_TOL
                  and token_gap <= TOKEN_TOL and mean_gap <= MEAN_TOL)
        agrees = agrees and ok
        record[head] = {"system_mean": float(g.mean()),
                        "reference_mean": float(w.mean()),
                        "mean_gap": mean_gap, "rms_gap": rms_gap,
                        "token_gap": token_gap, "agrees": ok}
    spec.emit(phase="reference", mean_tol=MEAN_TOL, rms_tol=RMS_TOL,
              token_tol=TOKEN_TOL, agrees=agrees, tokens=int(got[0].size),
              **record)
    return agrees
