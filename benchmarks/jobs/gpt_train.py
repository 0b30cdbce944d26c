"""The training job of a GPT-2-shaped model: the README path of the
program, mesh -> GPT -> FusedAdam -> init_sharded_optimizer ->
make_tp_dp_train_step(donate=True), as `chip_smoke.py::train_run` ran it
on the chip, with the benchmark's own correctness check before it and
the benchmark's own loop around it.

From the program it takes the system under test and nothing that
measures: the loop, the clock, the FLOP and byte counts, the peaks, the
trace reduction and the reference are all under `benchmarks/`.

A workload file's `params`:
    batch, seq          global batch (sequences) and their length
    tensor_parallel     tp; the data parallelism is chips / tp
    sequence_parallel   Megatron SP on the tp axis
    state_dtype         dtype of master weights and Adam moments
    lr                  Adam's step size
The model is always bf16 compute and logits, flash attention, fused
cross entropy, no dropout, no remat, donated state.
"""

from __future__ import annotations

import gc
import glob
import math
import os
import shutil
import time

from benchmarks.lib import hlo, train_loop, work
from benchmarks.lib.peaks import peaks_for

WARMUP_STEPS = 3
RING = 8                 # seeded batches kept on the device
TRACED_STEPS = 8         # profiled steps; the first is not steady
LOSS_AT = (12, 16)       # loss_after_16_steps: steps 13..16, 0-based slice
CHECK_SEQUENCES = 2
# The system computes in bf16 (each rounding off by up to 2^-9 relative)
# and rounds its logits to bf16; the reference is float32 throughout.
# Measured on the v5e at the published widths, twelve seeds (my chip
# runs, PR 24): the per-token losses of the two differ by 0.0096-0.0098
# rms for gpt2-medium and 0.0174-0.0184 for the 2048-wide model (larger
# logits, coarser bf16 steps), by up to 0.069 on a single token, and by
# 4e-5 to 1.25e-3 in the mean over the sample's 1024 or 2048 tokens,
# where rounding errors mostly cancel (one standard error of that mean
# is rms / sqrt(tokens), 5.5e-4 at most).  The bounds sit at 2.2 times
# the largest rms seen, 3.6 times the largest single token and 7
# standard errors of the mean, so that a check of some hundred runs
# does not trip on chance.  An fp8 GEMM rounds 32 times coarser than
# bf16 (2^-4 against 2^-9) and an int8 one 4 times or more, so either
# pushes the rms past its bound.
RMS_TOL = 0.04
TOKEN_TOL = 0.25
MEAN_TOL = 4e-3


def run(spec) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import tune
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.monitor.compile import RecompileSentry
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    p = spec.workload["params"]
    batch, seq, tp = p["batch"], p["seq"], p["tensor_parallel"]
    sizes = work.model_sizes(spec.config)
    devices = list(spec.devices)
    dp = len(devices) // tp
    if tp * dp != len(devices) or batch % dp or CHECK_SEQUENCES % dp:
        raise ValueError(f"batch {batch} and tp {tp} do not divide over "
                         f"{len(devices)} device(s)")
    if seq > sizes["positions"]:
        raise ValueError(f"seq {seq} is beyond the configuration's "
                         f"{sizes['positions']} positions")
    state_dtype = jnp.dtype(p["state_dtype"])
    cfg = GPTConfig(
        vocab_size=sizes["vocab"], seq_len=seq, hidden=sizes["hidden"],
        num_layers=sizes["layers"], num_heads=sizes["heads"],
        ffn_mult=sizes["ffn"] // sizes["hidden"], dropout=0.0,
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16, remat=False,
        use_flash_attention=True,
        sequence_parallel=bool(p["sequence_parallel"]))

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=tp,
                                       devices=devices)
    model = GPT(cfg)
    on_mesh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), model.partition_specs(),
        is_leaf=lambda s: isinstance(s, P))

    # the weights: one jitted call from the seed, born sharded, in bf16
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
    n_local = int(state.params.shape[0]) // tp   # flat elements a device
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
    flash_shape = (batch // dp, cfg.num_heads // tp, seq, cfg.head_dim)
    kernels = {"flash": hlo.kernels_writing(calls, math.prod(flash_shape)),
               "adam": hlo.kernels_writing(calls, n_local)}
    spec.emit(phase="compile", trace_lower_s=t1 - t0, compile_s=t2 - t1,
              tpu_custom_calls=len(calls),
              flash_kernels=len(kernels["flash"]),
              adam_kernels=len(kernels["adam"]),
              collectives=hlo.collectives(text),
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
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [_peak_bytes(s) for s in stats]
    placed = (_placement(state, devices, tp, stats)
              if len(devices) > 1 else "ok")
    first, last = log.segments[1]
    step_s = sorted(b - a for a, b in zip(log.completed_at[first:last],
                                          log.completed_at[first + 1:]))
    spec.emit(phase="window", losses=losses,
              step_s_p50=step_s[len(step_s) // 2],
              step_s_p90=step_s[len(step_s) * 9 // 10],
              step_s_max=step_s[-1], sentry=sentry.summary(),
              tune=tune.stats(), peak_bytes=peaks, placement=placed,
              memory_stats=stats[0])
    correct = bool(agrees and all(finite) and falling
                   and sentry.steady_recompiles == 0 and placed == "ok")
    if not correct:
        spec.emit(phase="incorrect", agrees=agrees, finite=all(finite),
                  falling=falling, placement=placed,
                  steady_recompiles=sentry.steady_recompiles)

    end_to_end = {"train_tokens_per_s": tokens_per_s,
                  "setup_s": window_started - spec.t0}
    if len(losses) >= LOSS_AT[1]:
        end_to_end["loss_after_16_steps"] = (
            sum(losses[slice(*LOSS_AT)]) / (LOSS_AT[1] - LOSS_AT[0]))
    observed = {
        "spans": {"trace_lower_s": t1 - t0, "compile_s": t2 - t1,
                  "dispatch_s": log.dispatch_s[WARMUP_STEPS:]},
        "counters": {"steady_recompiles": sentry.steady_recompiles},
        "tokens_per_s": tokens_per_s,
        "chips": len(devices),
        "peak_bytes": peaks,
        "kernels": kernels,
        "work": {
            "flops_per_token": work.gpt_train_flops_per_token(sizes, seq),
            # per device and per step: every layer's attention, and the
            # one pass over this device's share of the flat state
            "flash": {k: v * cfg.num_layers for k, v in
                      work.flash_attention_work(*flash_shape).items()},
            "adam_bytes": work.adam_bytes(n_local, state_dtype.itemsize,
                                          jnp.dtype(cfg.dtype).itemsize)},
        "peaks": (None if spec.rehearse
                  else peaks_for(devices[0].device_kind)),
        "xplane": xplane,
    }
    return {"correct": correct, "attempted": len(losses) - WARMUP_STEPS,
            "failed": sum(not ok for ok in finite[WARMUP_STEPS:]),
            "end_to_end": end_to_end, "observed": observed,
            "memory_peak_bytes": max(peaks)}


def _agrees_with_reference(spec, model, mesh, params, tokens, labels) -> bool:
    """Correctness, outside the window: the system's own per-token loss
    (bf16, flash kernel, fused cross entropy, the cell's mesh) on a
    seeded sample of sequences of the cell's length, against the
    configuration's plain float32 reference on the same weights (for
    tp > 1, the tp=1 view of them)."""
    import jax
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import qkv_as_tp1
    from apex_tpu.parallel.mesh import DP_AXIS, TP_AXIS
    from apex_tpu.transformer.tensor_parallel.cross_entropy import (
        vocab_parallel_cross_entropy,
    )

    cfg = model.c
    tp = mesh.shape[TP_AXIS]

    def token_losses(prm, tok, lab):
        logits = model.logits_local(prm, model.apply(prm, tok))
        return vocab_parallel_cross_entropy(
            logits, lab.T, axis_name=cfg.axis_name, fused=cfg.fused_xent)

    system = jax.jit(shard_map(
        token_losses, mesh=mesh,
        in_specs=(model.partition_specs(), P(DP_AXIS), P(DP_AXIS)),
        out_specs=P(None, DP_AXIS), check_vma=False))
    got = np.asarray(system(params, tokens, labels), np.float32).T  # (B, S)
    spec.emit(phase="system_forward")
    reference = spec.load("reference", spec.config["reference"])
    want = np.asarray(reference.token_losses(
        qkv_as_tp1(params, cfg, tp) if tp > 1 else params, tokens, labels,
        num_heads=cfg.num_heads, num_layers=cfg.num_layers,
        device=spec.devices[0]), np.float32)
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


def _traced_steps(spec, step, state, batches, log):
    """TRACED_STEPS steps under the profiler; (state, the .xplane.pb)."""
    import jax

    trace_dir = os.path.join(spec.out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the spans are TraceAnnotations
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        state = train_loop.run(step, state, batches, log, steps=TRACED_STEPS)
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return state, (found[-1] if found else None)


def _peak_bytes(stats: dict) -> int:
    """The most a device held.  `peak_bytes_in_use` counts arrays only;
    the scratch a loaded program keeps for its temporaries is
    `bytes_reserved` (on the v5e it grows by the compiler's temp size
    when the step is loaded, and stays).  So the peak is the larger of
    the arrays' own peak, reached while the state is built, and arrays
    plus scratch as they stand at the end of the window, which is what
    the device holds during every step."""
    during_steps = stats.get("bytes_in_use", 0) + stats.get(
        "bytes_reserved", 0)
    return int(max(stats.get("peak_bytes_in_use", 0), during_steps))


def _placement(state, devices, tp, stats) -> str:
    """The rule of chip_smoke.py::_placement: every device holds 1/tp of
    the rows of each flat optimizer buffer, and none holds more than
    twice the bytes of another.  "ok" or what is wrong."""
    for name in ("params", "exp_avg", "exp_avg_sq"):
        buf = getattr(state, name)
        rows = {s.device: s.data.shape[0] for s in buf.addressable_shards}
        if set(rows) != set(devices) or set(rows.values()) != {
                buf.shape[0] // tp}:
            return (f"{name}: shards {sorted(rows.values())} are not "
                    f"{buf.shape[0] // tp} rows on each device")
    in_use = [int(s.get("bytes_in_use", 0)) for s in stats]
    if all(in_use) and max(in_use) > 2 * min(in_use):
        return f"bytes_in_use is lopsided: {in_use}"
    return "ok"
