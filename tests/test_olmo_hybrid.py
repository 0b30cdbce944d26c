"""models.olmo_hybrid as Olmo-Hybrid shapes it (a Gated DeltaNet layer
with one decay a head and keys narrower than values, then a QK-normed
full-attention layer, post-normed blocks, a dense SwiGLU in each) over
one-document and packed rows, held to the plain reference the
benchmark keeps (`benchmarks/reference/olmo_hybrid.py`: float32
jax.numpy, the delta rule a token at a time with an exact reset, a
dense masked softmax, nothing from apex_tpu) on seeded random weights
at toy sizes; the norms' placement; a step through the step builder."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu.models.olmo_hybrid import (  # noqa: E402
    OlmoHybrid,
    OlmoHybridConfig,
)
from apex_tpu.parallel import mesh as M  # noqa: E402
from benchmarks.reference import olmo_hybrid as ref  # noqa: E402

EOD = 63
SEQ = 64
# the model's keys at toy sizes, the source's spelling: layer 0 Gated
# DeltaNet (3 heads, keys 8 and values 16 wide), layer 1 full attention
# (2 heads of 16): the two kinds of a period, each once
ARCH = dict(num_attention_heads=2, hidden_size=32, linear_num_key_heads=3,
            linear_key_head_dim=8, linear_value_head_dim=16,
            linear_allow_neg_eigval=True,
            layer_types=["linear_attention", "full_attention"],
            rms_norm_eps=1e-6, num_hidden_layers=2, eod_token_id=EOD)


def toy(**overrides):
    return OlmoHybrid(OlmoHybridConfig(**{**dict(
        vocab_size=64, hidden=32, num_layers=2, attention_layers=(1,),
        num_heads=2, head_dim=16, gdn_heads=3, gdn_key_dim=8,
        gdn_value_dim=16, intermediate_size=48, init_std=0.3, scan_chunk=16,
        eod_token_id=EOD), **overrides}))


@pytest.fixture(scope="module")
def mesh():
    M.destroy_model_parallel()
    yield M.initialize_model_parallel(devices=jax.devices()[:1])
    M.destroy_model_parallel()


def _rows(key, ends):
    """Rows of ids below EOD with an EOD at each row's `ends`."""
    rows = np.array(jax.random.randint(key, (len(ends), SEQ), 0, EOD))
    for row, at in zip(rows, ends):
        row[list(at)] = EOD
    tokens = jnp.asarray(rows)
    return tokens, jnp.roll(tokens, -1, axis=1)


@pytest.fixture(scope="module")
def batches():
    """{"packed": row 0 with documents of 1, 2 and more tokens and a
    boundary on and beside a chunk's edge of 16, row 1 one long
    document and a short last one; "one": two rows without an EOD}."""
    k0, k1 = jax.random.split(jax.random.PRNGKey(1))
    return {"packed": _rows(k0, [(0, 3, 4, 6, 15, 32, SEQ - 1), (49,)]),
            "one": _rows(k1, [(), ()])}


def on_mesh(model, mesh, fn, out_specs, inputs=2):
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(model.partition_specs(),) + (P(),) * inputs,
        out_specs=out_specs, check_vma=False))


# bf16: the band the benchmark's check allows a single token at the
# real sizes (float32 and its loss on both kinds of row are the
# gradients' fixture's business below).  The toy's widths take the
# compiled `jax.numpy` bodies; the flash call it shares with the KDA
# stacks is interpreted in `tests/test_kimi_linear.py`, the scan's and
# the conv stage's kernels at 96 / 192 in `tests/test_delta_rule.py`
# and `tests/test_conv_stage.py`
@pytest.mark.parametrize("rows,dtype,kernels,std,tol", [
    ("packed", "bfloat16", None, 0.06, 0.7)])
def test_token_losses_match_the_reference(mesh, batches, rows, dtype,
                                          kernels, std, tol):
    model = toy(dtype=jnp.dtype(dtype), flash_override=kernels or None,
                init_std=std)
    params = model.init(jax.random.PRNGKey(3))
    got, second, stats = on_mesh(
        model, mesh, model.token_losses, (P(), None, P()))(
        params, *batches[rows])
    want, none = ref.token_losses(params, *batches[rows], arch=ARCH)
    assert second is None and none is None and stats == []
    assert got.shape == (2, SEQ) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=tol)


@pytest.fixture(scope="module")
def gradients(mesh, batches):
    """{rows: (the model's (loss, grads), the reference's)}, float32."""
    model = toy()
    params = model.init(jax.random.PRNGKey(3))
    grad = on_mesh(model, mesh, jax.value_and_grad(model.loss),
                   (P(), model.partition_specs()))
    want = jax.jit(jax.value_and_grad(
        lambda p, tokens, labels: ref.loss(p, tokens, labels, arch=ARCH)))
    return {rows: (grad(params, *batch), want(params, *batch))
            for rows, batch in batches.items()}


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


GDN = ("q", "k", "v", "conv_q", "conv_k", "conv_v", "a", "a_log", "dt_bias",
       "beta", "gate", "o_norm']['weight", "proj")
FULL = ("q", "k", "v", "q_norm']['weight", "k_norm']['weight", "proj")
LEAVES = ([f"['block0']['attn']['{n}']" for n in GDN]
          + [f"['block1']['attn']['{n}']" for n in FULL]
          + ["['block0']['mlp']['gate_up']", "['block1']['mlp']['down']",
             "['block0']['ln1']['weight']", "['block1']['ln2']['weight']",
             "['embed']['weight']", "['head']['weight']",
             "['final_ln']['weight']"])


@pytest.mark.parametrize("rows", ["packed", "one"])
def test_loss_matches_the_reference(gradients, rows):
    (loss, _), (want, _) = gradients[rows]
    np.testing.assert_allclose(loss, want, rtol=2e-5)


# float32, the band of tests/test_kimi_linear.py: the largest gap of
# any leaf read 1.3e-5 of its largest entry on the packed rows, 2.6e-6
# on the one-document rows
@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("rows", ["packed", "one"])
def test_gradient_of_every_leaf_matches_the_reference(gradients, rows, leaf):
    (_, got), (_, want) = gradients[rows]
    got, want = _leaves(got)[leaf], _leaves(want)[leaf]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-4 * scale)


def test_no_leaf_is_left_out(gradients):
    (_, got), (_, want) = gradients["packed"]
    assert set(_leaves(got)) == set(_leaves(want))
    assert set(LEAVES) <= set(_leaves(got))


def test_the_post_norms_and_no_input_norm_are_the_references(mesh, batches):
    """A block adds each sublayer's output normed, and its sublayers
    read the residual stream as it is.  With the mixers' post-norm
    weight at 0 a block adds RMSNorm(FFN(x)) times the FFN's weight c: a
    token's addition has rms |c|, whatever x is; with x ten times as
    large the FFN reads ten times as much, and SwiGLU is not
    homogeneous, so the addition's direction moves (a pre-norm block
    would read RMSNorm(x), the same for both).  The model and the
    reference agree on every layer so weighed."""
    model = toy()
    params = model.init(jax.random.PRNGKey(4))
    for i in range(2):
        params[f"block{i}"]["ln1"]["weight"] = jnp.zeros((32,))
        params[f"block{i}"]["ln2"]["weight"] = jnp.full((32,), 0.5)
    block = jax.jit(shard_map(
        lambda p, x: model._block(0, p, x), mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params["block0"]), P()),
        out_specs=P(), check_vma=False))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 32))
    added = block(params["block0"], x) - x
    rms = jnp.sqrt(jnp.mean(added * added, axis=-1))
    np.testing.assert_allclose(rms, 0.5, rtol=1e-4)
    louder = block(params["block0"], 10 * x) - 10 * x
    cos = jnp.sum(added * louder, -1) / (
        jnp.linalg.norm(added, axis=-1) * jnp.linalg.norm(louder, axis=-1))
    assert float(jnp.min(cos)) < 0.99
    got = on_mesh(model, mesh, model.token_losses, (P(), None, P()))(
        params, *batches["packed"])[0]
    want = ref.token_losses(params, *batches["packed"], arch=ARCH)[0]
    np.testing.assert_allclose(got, want, atol=3e-4)


def test_a_step_through_the_step_builder(mesh, batches):
    """A step over packed rows through `make_tp_dp_train_step`, the
    mixers recomputed as the benchmark's cell runs them: the loss it
    reports is the reference's on the weights it started from, the
    stack routes nothing, and every sublayer's scope is opened.  (What
    the checkpoint keeps decides memory and time, not the gradient;
    `tests/test_kimi_linear.py` holds the policy's names.)"""
    from apex_tpu.monitor import scopes
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    model = toy(init_std=0.06, recompute_mixers=True)
    params = model.init(jax.random.PRNGKey(8))
    want = ref.loss(params, *batches["packed"], arch=ARCH)
    opt = FusedAdam(lr=3e-3, use_pallas=False)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)
    state, loss = step(state, *batches["packed"])
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-5)
    counts, overflow = on_mesh(model, mesh, model.routing_counts,
                               (P(), P()), inputs=1)(
        params, batches["packed"][0])
    assert counts.shape == (0, 0) and overflow.shape == (0,)

    owners = {owner for owner, _, _ in
              scopes.owners(scopes.step_text()).values()}
    assert {"block0/attn/segments", "final_ln", "head", "loss"} | {
        f"block1/attn/{s}" for s in ("qkv", "qknorm", "flash", "proj")} | {
        f"block0/attn/{s}" for s in (
            "qkv", "conv", "decay", "scan", "gate", "onorm", "proj")} | {
        f"block{i}/{s}" for i in range(2) for s in (
            "ln1", "ln2", "mlp/gate_up", "mlp/down")} <= owners


def test_without_an_eod_a_row_is_one_document(mesh, batches):
    model = toy(eod_token_id=None)
    assert model.documents(batches["packed"][0]) is None
    params = model.init(jax.random.PRNGKey(3))
    got = on_mesh(model, mesh, model.token_losses, (P(), None, P()))(
        params, *batches["packed"])[0]
    open_ = ref.token_losses(params, *batches["packed"], arch=ARCH,
                             boundaries=False)[0]
    np.testing.assert_allclose(got, open_, atol=3e-4)
