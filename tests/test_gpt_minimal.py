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


# ---------- flash attention in the projection's own layout, in the model ----

def _flash_cfg(**kw):
    # head_dim 64 and two local heads at tp = 2: one 128-lane head pair
    return _cfg(hidden=256, num_heads=4, use_flash_attention=True, **kw)


def _layouts(stats):
    """The calls by layout, without the score counters beside them."""
    return {k: stats[k] for k in ("projection_layout", "head_major")}


def _with_kernels(monkeypatch, in_place):
    """Run the flash kernels here (interpreted), and choose by shape
    alone whether the projection's layout can be taken: a VMEM cap of
    zero is a sequence that never fits."""
    from apex_tpu.ops import flash_attention as flash_mod
    monkeypatch.setattr(flash_mod, "use_pallas", lambda override=None: True)
    if not in_place:
        monkeypatch.setattr(flash_mod, "_QKV_LAYOUT_SEQ_CAP", 0)
    flash_mod.reset_stats()
    return flash_mod


@pytest.mark.parametrize("tp,sp", [(1, False), (2, True)],
                         ids=["tp1", "tp2_sp"])
def test_flash_in_projection_layout_leaves_loss_and_grads(tp, sp,
                                                          monkeypatch):
    """GPT's loss and every gradient with the kernels reading the packed
    projection where it lies are those of the split + transpose +
    head-major path, and of the dense softmax path, at tp = 1 and at
    tp = 2 with sequence parallelism; every layer takes the layout its
    shapes allow."""
    devices = jax.devices()[:2 * tp]
    cfg = _flash_cfg(sequence_parallel=sp)
    flash_mod = _with_kernels(monkeypatch, in_place=True)
    loss, grads = _loss_and_grads(cfg, tp, devices)
    assert _layouts(flash_mod.stats()) == {"projection_layout": LAYERS,
                                 "head_major": 0}
    flash_mod = _with_kernels(monkeypatch, in_place=False)
    old_loss, old = _loss_and_grads(cfg, tp, devices)
    assert _layouts(flash_mod.stats()) == {"projection_layout": 0,
                                 "head_major": LAYERS}
    dense_loss, dense = _loss_and_grads(
        _cfg(hidden=256, num_heads=4, sequence_parallel=sp), tp, devices)
    np.testing.assert_allclose(loss, old_loss, rtol=1e-6)
    np.testing.assert_allclose(loss, dense_loss, rtol=1e-5)
    for (path, g), o, w in zip(jax.tree_util.tree_leaves_with_path(grads),
                               jax.tree_util.tree_leaves(old),
                               jax.tree_util.tree_leaves(dense),
                               strict=True):
        scale = float(jnp.abs(w).max())
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(g, o, rtol=0, atol=1e-5 * scale,
                                   err_msg=name)
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-4 * scale,
                                   err_msg=name)
