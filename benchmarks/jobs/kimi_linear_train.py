"""The training job of a decoder that mixes Kimi Delta Attention and
latent attention without positions behind a leading dense layer and
over expert layers (Kimi-Linear), as one chip of its expert-parallel
group runs it over rows of packed documents: mesh -> HybridMoE ->
FusedAdam -> init_sharded_optimizer -> make_tp_dp_train_step(donate=
True), the path `hybrid_moe_train.py` drives for the Solar stack, with
the same loop around it.

From the program it takes the system under test and nothing that
measures: the loop, the clock, the FLOP and byte counts
(`lib/work_kimi_linear.py`), the peaks, the trace reduction, the
documents' generator and the reference are all under `benchmarks/`.

The configuration file says what the chip holds (layers, experts,
vocabulary rows) and which id closes a document; a workload file's
`params`:
    batch, seq          rows a step and their length
    documents           {"median", "sigma", "min", "max"}: a document's
                        length, its closing EOD included, is log-normal
                        (median, sigma), rounded and clipped to [min,
                        max]; a row is filled document by document and
                        the last is cut at the row's end
    tensor_parallel     1: the model's parallel axis is the experts'
    sequence_parallel   false
    state_dtype         dtype of master weights and Adam moments
    lr                  Adam's step size
    recompute_mixers    optional, false: true recomputes the inner
                        activations of the mixers in the backward
Every row of the ring and both check rows draw their own documents,
all from `--seed`; ids are uniform over the held rows but
`eod_token_id`, which stands at each document's last position and
nowhere else; labels are the row rolled by one.  The step takes the
tokens alone: the model derives the documents from them.

Before the step is built three checks run outside the window: the
model's per-token losses against the configuration's float32 reference
on two packed rows; the same against the reference told that the EOD is
an ordinary token, which has to fail (or the first check could not see
a mixer that forgot a boundary); and the chunked delta rule alone, with
a row's own resets, against the reference's recurrence at the step's
shape.  The model is always bf16 compute and logits with fp32 router
scores, decays and delta-rule state, flash attention, fused cross
entropy, no dropout, donated state.
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
from benchmarks.jobs.hybrid_moe_train import SLOW_STEP
from benchmarks.lib import hlo, train_loop, work_kimi_linear as work
from benchmarks.lib.peaks import peaks_for

# The system computes in bf16 (each rounding off by up to 2^-9 relative)
# and rounds its logits to bf16; the reference is float32 throughout,
# computes the delta rule a token at a time and sets its state to 0,
# exactly, at a document's first token.  Readings of each gap, on the
# v5e at the published widths, 2 packed rows of 8,192 tokens (my chip
# runs, PR 34):
#   the system against the reference, thirteen seeds: rms 0.0202-0.0425;
#     the worst single token 0.228-0.364; the mean over the 16,384
#     tokens 5e-6-3.1e-4 (one standard error of that mean is rms / sqrt(
#     tokens) = 3.3e-4 at most);
#   the reference with every GEMM's operands rounded to bfloat16, against
#     itself in float32 (five seeds): rms 0.0148-0.0161, worst token
#     0.244-0.292: the system is what bf16 gives, with its activations
#     and logits rounded besides;
#   the reference with every GEMM's operands rounded to float8_e5m2, the
#     nearest precision below bf16 that keeps its range (five seeds): rms
#     0.560-0.577, worst token 2.05-2.73, mean 6.0e-3-1.9e-2;
#   the boundary control, the system against the reference told that
#     the EOD is an ordinary token (thirteen seeds): rms 0.245-0.321, worst
#     token 2.67-4.26, mean 4.4e-4-5.2e-3; over the sixteen tokens that
#     follow a boundary alone the rms is 1.06-1.18 where the system
#     reads 0.018-0.042 against the true reference.
# Each bound lies between its two readings: the rms at 2.8 times the
# largest the system gave, half the boundary control's smallest and
# under a quarter of the fp8 reading's; the single token at 2.7 times
# the largest seen (a maximum over 16,384 tokens of a heavy tail reads
# higher on fresh seeds: a near-tie among a router's 256 scores that
# bf16 settles the other way swaps an expert), under half the fp8
# reading's smallest and 0.37 of the control's; the mean at 8 times the
# largest seen, 7.5 standard errors at the largest rms, and 0.42 of the
# fp8 reading's smallest.  The fp8 reading fails all three on every
# seed, the boundary control the rms and the single token on every seed
# (its mean on some): a mixer that forgot a boundary is seen.
# What these three cannot see: the reference with the delta rule's
# state rounded to bf16 after every token reads rms 0.022-0.024, worst
# token 0.26-0.39, as little as the system itself.  SCAN_TOL below is
# the bound such a scan fails.
RMS_TOL = 0.12
TOKEN_TOL = 1.0
MEAN_TOL = 2.5e-3

# The delta rule alone at the step's own shape (1 x 32 heads x 8,192)
# with a row's own resets in it, under the pinned slow decay of
# `hybrid_moe_train.py` (a state there lives about a thousand tokens, or
# until its document ends), against the reference's recurrence with its
# exact resets.  Readings on the v5e, thirteen seeds for the op, five
# for the controls (my chip runs, PR 34):
# the op 0.004358-0.004459 (bf16 operands in every product, a bf16
# output, a first token's decay pinned at -30); the recurrence with its
# state rounded to bf16 after every token 0.01623-0.01805, to float16
# 0.00195-0.00214.  The bound is 1.9 times the op's largest reading and
# 0.52 of the bf16 state's smallest.
SCAN_TOL = 0.0085


def model_config(config: dict, **overrides):
    """The program's HybridMoEConfig for a configuration file."""
    from apex_tpu.models.hybrid_moe import HybridMoEConfig

    s = work.sizes(config)
    return HybridMoEConfig(
        vocab_size=s["vocab"], hidden=s["hidden"], num_layers=s["layers"],
        attention_layers=s["attends"], attention_kind="latent",
        num_heads=s["heads"], kv_lora_rank=s["kv_rank"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
        v_head_dim=s["v"], kda_heads=s["kda_heads"],
        kda_head_dim=s["kda_dim"], conv_kernel=s["taps"],
        kda_rank=s["kda_rank"], allow_neg_eigval=False,
        first_k_dense_replace=s["dense"], intermediate_size=s["ffn"],
        moe_intermediate_size=s["expert_ffn"],
        n_routed_experts=s["published"], num_experts_per_tok=s["top_k"],
        n_shared_experts=s["shared"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["moe_renormalize"]),
        rms_norm_eps=config["rms_norm_eps"],
        experts_first=config.get("experts_first", 0),
        experts_count=s["held"],
        expert_rows_factor=float(config.get("expert_rows_factor", 2.0)),
        init_std=config.get("initializer_range", 0.02),
        eod_token_id=s["eod"], **overrides)


def packed_rows(rng, rows: int, seq: int, vocab: int, eod: int, lengths: dict):
    """`rows` rows of `seq` ids, documents packed end to end: (tokens
    (rows, seq) int32 numpy, the documents' lengths as they lie in the
    rows, a list a row).  `rng`: a `numpy.random.Generator`."""
    import numpy as np

    # uniform over the held rows but the EOD
    tokens = rng.integers(0, vocab - 1, (rows, seq), dtype=np.int32)
    tokens += tokens >= eod
    lie = []
    for row in tokens:
        ends = np.zeros(0, np.int64)
        while ends.size == 0 or ends[-1] < seq:
            drawn = np.rint(np.exp(rng.normal(
                math.log(lengths["median"]), lengths["sigma"], 64)))
            drawn = np.clip(drawn, lengths["min"], lengths["max"])
            ends = np.concatenate([ends, (ends[-1] if ends.size else 0)
                                   + np.cumsum(drawn.astype(np.int64))])
        ends = ends[:np.searchsorted(ends, seq) + 1]   # the last may be cut
        row[ends[ends <= seq] - 1] = eod
        starts = np.concatenate([[0], ends[:-1]])
        lie.append([int(n) for n in np.minimum(ends, seq) - starts])
    return tokens, lie


def run(spec) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import tune
    from apex_tpu.models.hybrid_moe import HybridMoE
    from apex_tpu.monitor.compile import RecompileSentry
    from apex_tpu.ops import delta_rule, flash_attention
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
    rows, lengths = packed_rows(
        np.random.default_rng(spec.seed + 1), RING * batch + CHECK_SEQUENCES,
        seq, cfg.vocab_size, cfg.eod_token_id, p["documents"])
    by_dp = NamedSharding(mesh, P(M.DP_AXIS))
    put = lambda x: jax.device_put(jnp.asarray(x), by_dp)
    ring = rows[:RING * batch].reshape(RING, batch, seq)
    batches = [(put(ring[i]), put(np.roll(ring[i], -1, axis=1)))
               for i in range(RING)]
    sample = put(rows[RING * batch:])
    sample_labels = put(np.roll(rows[RING * batch:], -1, axis=1))
    ring_lengths = [n for row in lengths[:RING * batch] for n in row]
    pairs_a_step = work.kept_pairs(ring_lengths) / RING
    spec.emit(phase="documents", ring_documents=len(ring_lengths),
              docs_per_step=len(ring_lengths) / RING,
              kept_pairs_per_token=pairs_a_step / (batch * seq),
              shortest=min(ring_lengths), longest=max(ring_lengths),
              check_documents=[len(row) for row in lengths[RING * batch:]])

    agrees = _agrees_with_reference(spec, model, mesh, params, sample,
                                    sample_labels)
    agrees = _scan_agrees_with_recurrence(
        spec, model, params, sample[:batch]) and agrees
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

    # what the step's own trace counts, not the checks'
    delta_rule.reset_stats()
    flash_attention.reset_stats()
    tune.reset_stats()
    t0 = time.perf_counter()
    lowered = step.lower(state, *batches[0])
    t1 = time.perf_counter()
    scan = delta_rule.stats()
    flash = flash_attention.stats()
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
              delta_rule=scan, flash=flash,
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
                     "kda_chunk": scan["chunk"],
                     # the step's own trace, kept from before the window
                     "flash_scores_computed": flash["scores_computed"],
                     "flash_scores_required": flash["scores_required"],
                     # of the pairs a causal mask keeps, the share that
                     # lies inside one document
                     "doc_pairs_share": pairs_a_step / (
                         batch * seq * (seq + 1) // 2),
                     "docs_per_step": len(ring_lengths) / RING},
        "tokens_per_s": tokens_per_s,
        "chips": 1,
        "peak_bytes": [peak],
        "kernels": kernels,
        "work": {
            "flops_per_token": work.train_flops_per_token(
                sizes, pairs_a_step / (batch * seq)),
            # per step: the latent layer's attention at the pairs the
            # ring's documents keep, the delta rule of every KDA layer,
            # the grouped GEMMs of every expert layer, one pass over the
            # flat state
            "flash": work.flash_attention_work(sizes, pairs_a_step,
                                               batch * seq),
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


def scan_gap(model, params, reference, tokens, seed, device,
             state_dtype=None) -> float:
    """The rms of (the program's `gated_delta_rule` - the reference's
    recurrence) over the rms of the recurrence's output, at (the rows
    of `tokens`, the model's heads, their length), with the resets of
    `tokens`' own documents: both read the q, k, v, g, beta that the
    first KDA layer's weights make of seeded unit-rms activations under
    the slowest decay the initialisation draws from.  With
    `state_dtype`, what the reference's own recurrence reads against
    itself when it rounds its state to that dtype after every token:
    the control the bound is set under."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.delta_rule import gated_delta_rule

    c = model.c
    layer = min(i for i in range(c.num_layers)
                if i not in c.attention_layers)
    slow = SLOW_STEP + math.log(-math.expm1(-SLOW_STEP))  # softplus^-1

    def inputs(attn, tokens, key):
        x = jax.random.normal(key, (*tokens.shape, c.hidden), jnp.float32)
        a = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))
        pinned = dict(attn, a_log=jnp.zeros_like(attn["a_log"]),
                      dt_bias=jnp.full_like(attn["dt_bias"], slow))
        docs = model.documents(tokens)
        return (*model.scan_inputs(pinned, a.astype(c.dtype), docs),
                docs.first)

    *args, first = jax.jit(inputs)(params[f"block{layer}"]["attn"], tokens,
                                   jax.random.PRNGKey(seed))
    want = np.asarray(reference.scan_outputs(*args, first, device=device))
    if state_dtype is None:
        # as the model calls it: the chunk and the heads a pass come
        # from the same place as the step's
        got = jax.jit(lambda *x: gated_delta_rule(
            *x[:5], resets=x[5], chunk=c.scan_chunk))(*args, first)
    else:
        got = reference.scan_outputs(*args, first, device=device,
                                     state_dtype=state_dtype)
    got = np.asarray(got, np.float32)
    return float(np.sqrt(np.mean(np.square(got - want))
                         / np.mean(np.square(want))))


def _scan_agrees_with_recurrence(spec, model, params, tokens) -> bool:
    """Correctness of the delta rule's precision and of its resets,
    outside the window: the op as the step runs it (bf16 operands, the
    tuned chunk, the state in float32, a first token's decay pinned) at
    the step's shape against the reference's float32 recurrence, a
    token at a time, its state set to 0 at every first token."""
    reference = spec.load("reference", spec.config["reference"])
    gap = scan_gap(model, params, reference, tokens, spec.seed + 2,
                   spec.devices[0])
    agrees = bool(gap <= SCAN_TOL)       # False for a nan
    spec.emit(phase="scan_check", gap=gap, tol=SCAN_TOL, agrees=agrees,
              slow_step=SLOW_STEP)
    return agrees


def gaps(got, want) -> dict:
    """The three readings the bounds are set on."""
    import numpy as np

    return {"rms_gap": float(np.sqrt(np.mean(np.square(got - want)))),
            "token_gap": float(np.max(np.abs(got - want))),
            "mean_gap": float(abs(got.mean(dtype=np.float64)
                                  - want.mean(dtype=np.float64)))}


def within(read: dict) -> bool:
    return bool(read["rms_gap"] <= RMS_TOL and read["token_gap"] <= TOKEN_TOL
                and read["mean_gap"] <= MEAN_TOL)


def system_losses(model, mesh, params, tokens, labels):
    """The system's own per-token losses, a row at a time, the step's
    own shape: the kernels take the shapes (and the tuned
    configurations) the window will use."""
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
    return np.concatenate([
        np.asarray(system(params, tokens[i:i + 1], labels[i:i + 1]),
                   np.float32) for i in range(tokens.shape[0])])


def _agrees_with_reference(spec, model, mesh, params, tokens, labels) -> bool:
    """Correctness, outside the window: the system's own per-token
    losses (bf16, the flash kernels under their segment mask, the
    chunked delta rule with its resets, the masked taps, the grouped
    GEMMs, the fused cross entropy) on two seeded packed rows of the
    cell's length, against the configuration's plain float32 reference
    on the same weights and the same share; and the control: against
    the same reference told that the EOD is an ordinary token the
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
    forgot = gaps(got, reference_losses(boundaries=False))
    agrees = bool(np.isfinite(got).all() and within(read))
    boundaries_seen = not within(forgot)
    spec.emit(phase="reference", system_mean=float(got.mean()),
              reference_mean=float(want.mean()), **read, mean_tol=MEAN_TOL,
              rms_tol=RMS_TOL, token_tol=TOKEN_TOL, agrees=agrees,
              tokens=int(got.size))
    spec.emit(phase="boundary_control", **forgot,
              fails_as_it_must=boundaries_seen)
    return agrees and boundaries_seen
