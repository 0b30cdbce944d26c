"""Tensor+data-parallel training step builder for transformer models.

≡ the reference's Megatron training driver shape
(tests/L0/run_transformer/test_gpt_minimal.py:146-220 +
schedules/common.py forward/backward_step): one jitted SPMD program per
step — shard-local forward/backward with TP collectives inside autodiff,
dp-pmean of grads, fused optimizer on the LOCAL param shard (each rank
owns and updates exactly its shard — optimizer state is tp-sharded by
construction, which is also the natural ZeRO-over-tp layout).

Chunked compute/collective overlap (ISSUE 18) rides through here
untouched: `GPTConfig.overlap_chunks` reaches the TP layers at model
construction, so the step this builder jits contains the chunked
ppermute-ring / chunked-reduce pipelines (parallel/overlap.py) in
BOTH directions — the custom_vjp spellings keep the backward chunked
under the value_and_grad below, and at chunks == 1 the traced program
is byte-identical to the pre-overlap step.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor import scopes
from apex_tpu.monitor.compile import startup
from apex_tpu.optimizers import flat as F
from apex_tpu.parallel.mesh import DP_AXIS, PP_AXIS


def init_sharded_optimizer(optimizer, model, params, mesh):
    """Create optimizer state over the LOCAL param shards.

    The flat fp32 buffers come out tp-sharded (concat of per-rank local
    flats ⇒ P("tp") on dim 0), replicated over dp.
    """
    specs = model.partition_specs()

    def local_init(p):
        return optimizer.init(p)

    with startup.span("init_sharded_optimizer"):
        state_struct = jax.eval_shape(
            lambda p: optimizer.init(p), params)  # sets optimizer.spec? no —
        # eval_shape traces on GLOBAL shapes; re-derive the local spec by
        # tracing inside shard_map below (optimizer.init sets .spec there).

        # buffers sharded over tp (dim 0), step replicated
        out_specs = type(state_struct)(
            *([P()] + [P(("pp", "tp"))] * (len(state_struct) - 1)))
        init_fn = jax.jit(shard_map(local_init, mesh=mesh, in_specs=(specs,),
                                    out_specs=out_specs, check_vma=False))
        return init_fn(params)


def make_tp_dp_train_step(model, optimizer, mesh, *,
                          loss_fn: Optional[Callable] = None,
                          donate: bool = True,
                          pp_partial_grads: Optional[bool] = None):
    """Returns step(opt_state, tokens, labels[, key]) ->
    (opt_state, loss).  `loss_fn(params, tokens, labels)` defaults to
    model.loss.  Batch is sharded over dp; params/optimizer over tp.

    pp_partial_grads: whether pp-replicated leaves carry PARTIAL grads
    that must be psum'd over pp (True for pipelined models, whose
    embedding/head grads land on different stages — ≡ the reference's
    embedding-group allreduce).  A non-pipelined model on a pp>1 mesh
    computes COMPLETE identical grads on every stage, where the psum
    would scale them by pp.  Default: infer from the model's
    `pipeline_parallel_size`/`pp` attribute.
    """
    specs = model.partition_specs()
    lf = loss_fn or (lambda p, t, l: model.loss(p, t, l))
    if pp_partial_grads is None:
        pp_partial_grads = max(
            getattr(model, "pp", 1),
            getattr(model, "pipeline_parallel_size", 1)) > 1

    def local_step(opt_state, tokens, labels):
        # NOTE: differentiating w.r.t. the flat param view (so grads
        # arrive pre-flattened) was tried and is ~40% SLOWER: the
        # unflatten-transpose becomes one full-buffer scatter-add per
        # leaf.  Per-leaf grads + one concatenate is the fast shape.
        with jax.named_scope("unflatten"):
            params = F.unflatten(opt_state.params, optimizer.spec)

        loss, grads = jax.value_and_grad(lambda p: lf(p, tokens, labels))(
            params)
        with jax.named_scope("dp_reduce"):
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, DP_AXIS), grads)
        if pp_partial_grads:
            # pp-REPLICATED leaves (tied embedding, position embeddings,
            # final LN) get per-stage PARTIAL grads under the pipeline —
            # embed-side on stage 0, head-side on the last stage — so
            # each stage's optimizer copy would diverge without summing
            # them.  ≡ the reference's embedding-group allreduce
            # (parallel_state.py:319-407).
            def _pp_sync(g, spec):
                names = set()
                for entry in spec:  # P is tuple-like: None | str | tuple
                    (names.update(entry) if isinstance(entry, tuple)
                     else names.add(entry))
                if PP_AXIS in names:
                    return g  # pp-sharded leaf: its grad is stage-local
                return jax.lax.psum(g, PP_AXIS)
            with jax.named_scope("pp_sync"):
                grads = jax.tree_util.tree_map(_pp_sync, grads, specs)
        with jax.named_scope("optimizer"):
            _, new_state = optimizer.step(opt_state, grads)
        with jax.named_scope("dp_reduce"):
            return new_state, jax.lax.pmean(loss, DP_AXIS)

    state_spec_leaves = None

    def _state_specs(state):
        return type(state)(*([P()] + [P(("pp", "tp"))] * (len(state) - 1)))

    def build(opt_state):
        out_specs = (_state_specs(opt_state), P())
        in_specs = (_state_specs(opt_state), P(DP_AXIS), P(DP_AXIS))
        return jax.jit(
            shard_map(local_step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
            donate_argnums=(0,) if donate else ())

    # build() depends only on the state STRUCTURE (out_specs count its
    # fields), so the cache is keyed on that; the jitted fn inside
    # re-specializes per input shape/dtype on its own
    cache = {}

    def _jitted_for(opt_state, *batch):
        k = jax.tree_util.tree_structure(opt_state)
        fn = cache.get(k)
        if fn is None:
            with startup.span("make_tp_dp_train_step.build"):
                fn = build(opt_state)
                # once a program, not a step: monitor.scopes can then
                # say which scope owns each instruction of the step that
                # ran.  Under another transformation (the linter's
                # make_jaxpr) the arguments are tracers and nothing will
                # run: such a build is not kept, so the first real call
                # registers
                if scopes.register(local_step.__name__, fn,
                                   (opt_state, *batch)):
                    cache[k] = fn
        return fn

    def step(opt_state, tokens, labels):
        return _jitted_for(opt_state, tokens, labels)(
            opt_state, tokens, labels)

    def lower(opt_state, tokens, labels):
        return _jitted_for(opt_state, tokens, labels).lower(
            opt_state, tokens, labels)

    def _cache_size():
        # aggregate over the per-structure jits so RecompileSentry's
        # cache poll sees EVERY compile — including the donated-layout
        # recompile no argument-signature change announces (without
        # this the sentry falls back to signature-only detection and
        # the bench gate would miss that class entirely)
        return sum(fn._cache_size() for fn in cache.values())

    # compile & HBM observatory handles (monitor.compile.analyze_step
    # / RecompileSentry): AOT-audit the exact program, label the
    # budget table, verify donation — see parallel/ddp.py
    step.lower = lower
    step._cache_size = _cache_size
    step.donate_argnums = (0,) if donate else ()
    step.arg_names = ("opt_state", "tokens", "labels")
    # mesh axes for the static linter's collective-axis check
    # (apex_tpu.lint CL201) and the comms observatory's replica-group
    # mapping (monitor.comms, ISSUE 7) — see parallel/ddp.py
    step.mesh_axis_names = tuple(str(a) for a in mesh.axis_names)
    step.mesh_axis_sizes = tuple(int(s) for s in mesh.devices.shape)
    return step
