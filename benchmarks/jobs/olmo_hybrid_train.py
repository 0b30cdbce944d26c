"""The training job of a dense decoder that mixes Gated DeltaNet and
QK-normed full attention under post-norms (Olmo-Hybrid), as one
pipeline stage of whole layers runs it over rows of packed documents:
mesh -> OlmoHybrid -> FusedAdam -> init_sharded_optimizer ->
make_tp_dp_train_step(donate=True), the path `kimi_linear_train.py`
drives for the Kimi-Linear stack, with the same loop around it.

From the program it takes the system under test and nothing that
measures: the loop, the clock, the FLOP and byte counts
(`lib/work_olmo_hybrid.py`), the peaks, the trace reduction, the
documents' generator (`kimi_linear_train.packed_rows`) and the
reference are all under `benchmarks/`.

The configuration file says what the chip holds (layers and their
kinds, vocabulary rows) and which id closes a document; a workload
file's `params`:
    batch, seq          rows a step and their length
    documents           {"median", "sigma", "min", "max"}: a document's
                        length, its closing EOD included, is log-normal
                        (median, sigma), rounded and clipped to [min,
                        max]; a row is filled document by document and
                        the last is cut at the row's end
    tensor_parallel     1: a stage holds its layers whole
    sequence_parallel   false
    state_dtype         dtype of master weights and Adam moments
    lr                  Adam's step size
    recompute_mixers    optional, false: true keeps of a delta-rule
                        mixer its input and what `hybrid_moe.KEPT` names
                        and recomputes the rest in the backward
Every row of the ring and both check rows draw their own documents,
all from `--seed`; ids are uniform over the held rows but
`eod_token_id`, which stands at each document's last position and
nowhere else; labels are the row rolled by one.  The step takes the
tokens alone: the model derives the documents from them.

Before the step is built three checks run outside the window: the
model's per-token losses against the configuration's float32 reference
on two packed rows; the same against the reference told that the EOD is
an ordinary token, which has to fail; and the chunked delta rule alone,
with one decay a head and a row's own resets, against the reference's
recurrence at the step's shape.  A fourth reads the compiled step
itself: its first step, the first of the warm-up, on the ring's first
row, against `jax.grad` of the reference's loss there and an Adam step
in float32 (`_first_step_agrees`).  The model is always bf16 compute
and logits with fp32 decays and delta-rule state, flash attention,
fused cross entropy, no dropout, donated state.
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
from benchmarks.jobs.kimi_linear_train import (
    gaps,
    packed_rows,
    scan_gap,
    system_losses,
)
from benchmarks.lib import hlo, train_loop, work_olmo_hybrid as work
from benchmarks.lib.peaks import peaks_for

# The system computes in bf16 and rounds its logits to bf16; the
# reference is float32 throughout, computes the delta rule a token at a
# time and sets its state to 0, exactly, at a document's first token.
# Readings of each gap, on the v5e at the published widths, 2 packed
# rows of 8,192 tokens:
#   the system against the reference, fourteen seeds: rms 0.0239-0.0255;
#     the worst single token 0.111-0.245; the mean over the 16,384 tokens
#     4.3e-6-5.3e-4 (one standard error of that mean is rms / sqrt(
#     tokens) = 2.0e-4);
#   the reference with every GEMM's operands rounded to bfloat16, against
#     itself in float32 (two seeds): rms 0.0088-0.0089, worst token
#     0.046-0.049: the system is what bf16 gives, with its activations
#     and logits rounded besides;
#   the reference with every GEMM's operands rounded to float8_e5m2, the
#     nearest precision below bf16 that keeps its range (two seeds): rms
#     0.787-0.788, worst token 3.19-3.40, mean 0.0146-0.0285;
#   the boundary control, the system against the reference told that
#     the EOD is an ordinary token (fourteen seeds): rms 0.352-0.543,
#     worst token 3.79-6.34, mean 2.3e-4-5.0e-3.
# Each bound lies between its two readings: the rms at 3.1 times the
# largest the system gave, 0.23 of the boundary control's smallest and
# 0.10 of the fp8 reading's; the single token at 4.1 times the largest
# seen (a maximum over 16,384 tokens reads higher on fresh seeds), under
# a third of the fp8 reading's smallest and 0.26 of the control's; the
# mean at 4.7 times the largest seen, 12 standard errors, and 0.17 of
# the fp8 reading's smallest.  The fp8 reading fails all three on every
# seed, the boundary control the rms and the single token on every
# seed: a mixer that forgot a boundary is seen.  What these cannot see,
# a state or decays rounded to bf16, SCAN_TOL below sees.
RMS_TOL = 0.08
TOKEN_TOL = 1.0
MEAN_TOL = 2.5e-3

# The delta rule alone at the step's own shape (1 x 30 heads x 8,192,
# keys 96 and values 192 wide, one decay a head) with a row's own resets
# in it, under the slow decay `hybrid_moe_train.SLOW_STEP` pins (a state
# lives about a thousand tokens, or until its document ends), against
# the reference's recurrence with its exact resets.  Readings on the v5e
# the op 0.00577-0.00600 over fourteen seeds
# (bf16 operands in every product, a bf16 output, a first token's decay
# pinned at -30, the tuned chunk of 128 and ten heads a pass); the
# recurrence with its state rounded to bf16 after every token
# 0.01378-0.01404, with a token's decay factor rounded to bf16
# 0.04023-0.04188, its state rounded to float16 0.00172-0.00175 (two
# seeds each).  The bound is 1.5 times the op's largest reading and 0.65
# of the bf16 state's smallest.
SCAN_TOL = 0.009

# The compiled step's first step (the backward the window times: the
# scan's Pallas backward with one decay a head, the two-width conv
# stage's, the mixers' recompute, the flash backward, the flat view and
# the Adam kernel) on the ring's first row, against the reference's
# `jax.grad` on the same weights (float32, every product at "highest",
# the gradient kept in the weights' bf16) and its Adam step in float32
# stored in the state's dtype.  Read a leaf at a time, the worst leaf
# counts:
#   grad_gap    |g - g_ref| / |g_ref|, g read back from the first
#               moment (m = (1 - beta1) g after one step);
#   change_gap  | |p1 - p0| - |p1_ref - p0| | / |p1_ref - p0|: the norm
#               of a leaf's change against the reference's.  A state
#               left unchanged reads 1.  Not the norm of the
#               difference: a first Adam step moves an element by lr
#               times the sign of its gradient, and a gradient within
#               rounding of 0 flips its sign under bf16.
# Readings on the v5e at the published widths, the ring's first row:
#   the system, nine seeds: grad_gap 0.117-0.205 (the worst leaf a
#     Gated DeltaNet q or its taps; the median leaf 0.06-0.08),
#     change_gap 0.00036-0.0022;
#   the reference with every product's operands rounded to bf16, its
#     activations float32 (one seed): grad_gap 0.058, the median leaf
#     0.025: the system keeps its activations and residual stream in
#     bf16 besides, which takes it to twice that and more;
#   the reference with every product's operands rounded to
#     float8_e5m2, the nearest precision below bf16 (one seed):
#     grad_gap 1.0, the median leaf 1.0, the least 0.69: a mean over
#     8,192 tokens' cotangents underflows there, and most leaves' whole
#     gradient with it.
# GRAD_TOL is 2.4 times the largest the system gave and half the
# float8 reading; CHANGE_TOL is 45 times the largest change reading
# and a tenth of what a state left unchanged reads.  The float8
# reference fails GRAD_TOL; a wrong gradient of a leaf (a decay's, a
# key's) left out or of the wrong sign reads 1 or more and fails it too.
GRAD_TOL = 0.5
CHANGE_TOL = 0.1


WINDOW = 2            # the log's segments: the checked step, warm-up, window


def model_config(config: dict, **overrides):
    """The program's OlmoHybridConfig for a configuration file."""
    from apex_tpu.models.olmo_hybrid import OlmoHybridConfig

    s = work.sizes(config)
    return OlmoHybridConfig(
        vocab_size=s["vocab"], hidden=s["hidden"], num_layers=s["layers"],
        attention_layers=s["attends"], num_heads=s["heads"],
        head_dim=s["head_dim"], gdn_heads=s["gdn_heads"],
        gdn_key_dim=s["key_dim"], gdn_value_dim=s["value_dim"],
        conv_kernel=s["taps"], allow_neg_eigval=s["neg_eigval"],
        intermediate_size=s["ffn"], rms_norm_eps=config["rms_norm_eps"],
        init_std=config.get("initializer_range", 0.02),
        eod_token_id=s["eod"], **overrides)


def run(spec) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import tune
    from apex_tpu.models.olmo_hybrid import OlmoHybrid
    from apex_tpu.monitor.compile import RecompileSentry
    from apex_tpu.ops import conv_stage, delta_rule, flash_attention
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    p = spec.workload["params"]
    batch, seq = p["batch"], p["seq"]
    sizes = work.sizes(spec.config)
    devices = list(spec.devices)
    if (p["tensor_parallel"] != 1 or p["sequence_parallel"]
            or len(devices) != 1):
        raise ValueError("this job runs one pipeline stage of whole "
                         "layers: tensor_parallel 1, one device")
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
    model = OlmoHybrid(cfg)
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
    first_row = _reference_first_step(spec, params, *batches[0])
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
    conv_stage.reset_stats()
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
              delta_rule=scan, conv_stage=conv_stage.stats(), flash=flash,
              argument_bytes=int(memory.argument_size_in_bytes),
              temp_bytes=int(memory.temp_size_in_bytes),
              generated_code_bytes=int(memory.generated_code_size_in_bytes))
    del lowered, compiled, text
    if not spec.rehearse and not (kernels["flash"] and kernels["adam"]):
        raise RuntimeError(
            f"flash kernels {len(kernels['flash'])}, Adam kernels "
            f"{len(kernels['adam'])} among {len(calls)} tpu_custom_call(s): "
            "an op silently took its jnp reference instead of its kernel")
    if scan["scalar_calls"] != sizes["gdn"]:
        raise RuntimeError(
            f"{scan['scalar_calls']} delta-rule calls with a head's decay "
            f"traced for {sizes['gdn']} Gated DeltaNet layers")

    sentry = RecompileSentry(step, name=spec.name, warn=False)
    log = train_loop.StepLog()
    # the warm-up's first step is the one checked
    state = train_loop.run(sentry, state, batches, log, steps=1)
    agrees = _first_step_agrees(spec, opt, state, first_row) and agrees
    del first_row
    state = train_loop.run(sentry, state, batches, log,
                           steps=WARMUP_STEPS - 1)
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
    tokens_per_s = train_loop.rate_per_s(log, WINDOW, batch * seq)

    # ---- after -----------------------------------------------------------
    losses = log.losses
    finite = [math.isfinite(v) for v in losses]
    falling = (len(losses) >= 16
               and sum(losses[-8:]) / 8 < sum(losses[:8]) / 8)
    stats = devices[0].memory_stats() or {}
    peak = _peak_bytes(stats)

    first, last_step = log.segments[WINDOW]
    step_s = sorted(b - a for a, b in zip(
        log.completed_at[first:last_step],
        log.completed_at[first + 1:]))
    spec.emit(phase="window", losses=losses,
              step_s_p50=step_s[len(step_s) // 2],
              step_s_p90=step_s[len(step_s) * 9 // 10],
              step_s_max=step_s[-1], sentry=sentry.summary(),
              tune=tune.stats(), peak_bytes=[peak], memory_stats=stats)
    correct = bool(agrees and all(finite) and falling
                   and sentry.steady_recompiles == 0)
    if not correct:
        spec.emit(phase="incorrect", agrees=agrees, finite=all(finite),
                  falling=falling,
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
            # per step: the attending layer at the pairs the ring's
            # documents keep, the delta rule of every Gated DeltaNet
            # layer, one pass over the flat state
            "flash": work.flash_attention_work(sizes, pairs_a_step,
                                               batch * seq),
            "scan": work.scan_work(sizes, batch, seq),
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


def _scan_agrees_with_recurrence(spec, model, params, tokens) -> bool:
    """Correctness of the delta rule's precision and of its resets,
    outside the window: the op as the step runs it (bf16 operands, the
    tuned chunk, one decay a head, the state and the decays in float32,
    a first token's decay pinned) at the step's shape against the
    reference's float32 recurrence, a token at a time, its state set to
    0 at every first token."""
    reference = spec.load("reference", spec.config["reference"])
    gap = scan_gap(model, params, reference, tokens, spec.seed + 2,
                   spec.devices[0])
    agrees = bool(gap <= SCAN_TOL)       # False for a nan
    spec.emit(phase="scan_check", gap=gap, tol=SCAN_TOL, agrees=agrees,
              slow_step=SLOW_STEP)
    return agrees


def reference_gradient(spec, params, tokens, labels, matmul_dtype=None):
    """The configuration's reference's `jax.grad` of its mean loss over
    `tokens` at `params`, on the host: a list of the leaves' gradients
    in `params`' leaf order and dtype."""
    import jax

    reference = spec.load("reference", spec.config["reference"])
    grad = jax.jit(jax.grad(lambda p, t, l: reference.loss(
        p, t, l, arch=spec.config, device=spec.devices[0],
        matmul_dtype=matmul_dtype)))
    return [jax.device_get(g) for g in
            jax.tree_util.tree_leaves(grad(params, tokens, labels))]


def _reference_first_step(spec, params, tokens, labels) -> dict:
    """What the first step is held to, made before the state exists
    (the device holds the weights, the reference's gradient and its
    recomputation alone): the leaves' names, the weights the step
    starts from and the reference's gradient, all on the host."""
    import jax

    t0 = time.perf_counter()
    leaves = jax.tree_util.tree_leaves_with_path(params)
    want = {"names": [jax.tree_util.keystr(k) for k, _ in leaves],
            "before": [jax.device_get(x) for _, x in leaves],
            "grad": reference_gradient(spec, params, tokens, labels)}
    spec.emit(phase="reference_gradient", seconds=time.perf_counter() - t0)
    return want


def _first_step_agrees(spec, opt, state, want) -> bool:
    """Correctness of the compiled step's backward and update, outside
    the window: after its first step, the state's first moment and
    weights a leaf at a time against the reference's gradient and its
    Adam step (`GRAD_TOL`, `CHANGE_TOL`), the norms taken on the
    device in float32, one leaf there at a time beside the state."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    b1, b2 = opt.beta1, opt.beta2
    narrow = jnp.finfo(state.params.dtype)

    @functools.partial(jax.jit, static_argnums=2)
    def cut(flat, at, size):
        return jax.lax.dynamic_slice(flat, (at,), (size,))

    @jax.jit
    def norms(p, m, p0, g):
        p0, g = p0.astype(jnp.float32), g.astype(jnp.float32)
        # Adam's first step in float32, stored in the state's dtype (not
        # a pair of casts, which the compiler may drop as excess
        # precision)
        step = (1 - b1) * g / (1 - b1) / (
            jnp.sqrt((1 - b2) * g * g / (1 - b2)) + opt.eps)
        after = jax.lax.reduce_precision(
            p0 - opt.lr * step, narrow.nexp, narrow.nmant)
        return jnp.stack([jnp.linalg.norm(x) for x in (
            m.astype(jnp.float32) / (1 - b1) - g, g,
            p.astype(jnp.float32) - p0, after - p0)])

    read = np.array([np.asarray(norms(
        cut(state.params, at, size), cut(state.exp_avg, at, size),
        *(jax.device_put(x.reshape(-1), spec.devices[0]) for x in (p0, g))))
        for at, size, p0, g in zip(opt.spec.offsets, opt.spec.sizes,
                                   want["before"], want["grad"])],
        np.float64)
    by_grad = ratios(read[:, 0], read[:, 1])
    by_change = ratios(np.abs(read[:, 2] - read[:, 3]), read[:, 3])
    worst_g, worst_c = int(np.argmax(by_grad)), int(np.argmax(by_change))
    gaps = {"grad_gap": float(by_grad[worst_g]),
            "change_gap": float(by_change[worst_c])}
    agrees = bool(gaps["grad_gap"] <= GRAD_TOL
                  and gaps["change_gap"] <= CHANGE_TOL)   # False for a nan
    spec.emit(phase="first_step", **gaps, grad_tol=GRAD_TOL,
              change_tol=CHANGE_TOL, agrees=agrees,
              grad_leaf=want["names"][worst_g],
              change_leaf=want["names"][worst_c],
              by_leaf={n: [float(g), float(c)] for n, g, c in zip(
                  want["names"], by_grad, by_change)})
    return agrees


def ratios(a, b):
    """a / b of two arrays of norms: 0 where both are 0, inf where b
    alone is."""
    import numpy as np

    safe = np.where(b > 0, b, 1.0)
    return np.where(b > 0, a / safe, np.where(a > 0, np.inf, 0.0))


def within(read: dict) -> bool:
    return bool(read["rms_gap"] <= RMS_TOL and read["token_gap"] <= TOKEN_TOL
                and read["mean_gap"] <= MEAN_TOL)


def _agrees_with_reference(spec, model, mesh, params, tokens, labels) -> bool:
    """Correctness, outside the window: the system's own per-token
    losses (bf16, the flash kernels under their segment mask, the
    two-width conv stage, the chunked delta rule with one decay a head
    and its resets, the post-norms, the fused cross entropy) on two
    seeded packed rows of the cell's length, against the configuration's
    plain float32 reference on the same weights and the same share; and
    the control: against the same reference told that the EOD is an
    ordinary token the system has to read outside the bounds."""
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
