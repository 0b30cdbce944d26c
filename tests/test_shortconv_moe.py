"""models.shortconv_moe and ops.short_conv, held to the plain reference
the benchmark keeps (`benchmarks/reference/lfm2_moe.py`: float32
jax.numpy, nothing from apex_tpu) on seeded random weights at toy
sizes; and what `HeldExpertsLM` learnt for it (a tied head) held to
what the stacks that tie nothing traced before."""

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

from apex_tpu.models.shortconv_moe import (  # noqa: E402
    ShortConvMoE,
    ShortConvMoEConfig,
)
from apex_tpu.moe import HeldExpertsMLP  # noqa: E402
from apex_tpu.ops.short_conv import gated_short_conv  # noqa: E402
from apex_tpu.parallel import mesh as M  # noqa: E402
from benchmarks.reference import lfm2_moe as ref  # noqa: E402

# the family's keys at toy sizes: two dense layers (both conv) and one
# period attention conv conv conv of expert layers; experts [4, 12) of
# 16 held, 3 a token; 4 query heads on 2 kv heads of 8
KINDS = ("conv", "conv", "full_attention", "conv", "conv", "conv")
ARCH = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            rope_theta=1e4, norm_eps=1e-5, num_hidden_layers=6,
            layer_types=list(KINDS), num_dense_layers=2,
            num_experts_per_tok=3, experts_first=4, num_experts=8,
            routed_scaling_factor=1.0, norm_topk_prob=True,
            tie_word_embeddings=True)


def toy(**overrides):
    return ShortConvMoE(ShortConvMoEConfig(**{**dict(
        vocab_size=64, hidden=32, num_layers=6,
        layer_types=tuple("attention" if k == "full_attention" else k
                          for k in KINDS),
        num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e4,
        num_dense_layers=2, intermediate_size=48, moe_intermediate_size=8,
        n_routed_experts=16, num_experts_per_tok=3, experts_first=4,
        experts_count=8, init_std=0.3), **overrides}))


@pytest.fixture(scope="module")
def mesh():
    M.destroy_model_parallel()
    yield M.initialize_model_parallel(devices=jax.devices()[:1])
    M.destroy_model_parallel()


@pytest.fixture(scope="module")
def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    return tokens, jnp.roll(tokens, -1, axis=1)


def on_mesh(model, mesh, fn, out_specs):
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(model.partition_specs(), P(), P()),
        out_specs=out_specs, check_vma=False))


def _leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------- the model and its reference -------------------------

BF16_STD = 0.06       # test_mla_moe.py says why


@pytest.mark.parametrize("flash", [None, True])
def test_logits_match_the_reference(mesh, batch, flash):
    model = toy(flash_override=flash)
    params = model.init(jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        got = on_mesh(
            model, mesh,
            lambda p, t, l: model.logits_local(p, model.apply(p, t)),
            P())(params, *batch)
    want = ref.logits(params, batch[0], arch=ARCH)
    assert got.shape == (2, 32, 64)
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=0)
    assert float(jnp.abs(want).max()) > 1.0     # no flat function


@pytest.mark.parametrize("dtype,flash,std,tol", [
    (jnp.float32, None, 0.3, 2e-5), (jnp.float32, True, 0.3, 2e-5),
    (jnp.bfloat16, None, BF16_STD, 0.25)])
def test_token_losses_match_the_reference(mesh, batch, dtype, flash, std,
                                          tol):
    model = toy(dtype=dtype, flash_override=flash, init_std=std)
    params = model.init(jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        got = on_mesh(model, mesh,
                      lambda p, t, l: model.token_losses(p, t, l)[0],
                      P())(params, *batch)
    want = ref.token_losses(params, *batch, arch=ARCH)[0]
    assert got.shape == (2, 32) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)
    if dtype == jnp.bfloat16:      # and far closer than the band on the whole
        assert float(jnp.sqrt(jnp.mean((got - want) ** 2))) < 0.04


def test_the_reference_with_its_taps_reversed_is_another_network(batch):
    """The control the benchmark's check has to fail: a convolution
    that took the wrong tap for the current token."""
    params = toy().init(jax.random.PRNGKey(0))
    want = ref.token_losses(params, *batch, arch=ARCH)[0]
    turned = ref.token_losses(params, *batch, arch=ARCH,
                              taps_reversed=True)[0]
    assert float(jnp.sqrt(jnp.mean((turned - want) ** 2))) > 0.1


@pytest.fixture(scope="module")
def gradients(mesh, batch):
    """{dtype name: (system's loss and gradients, reference's)}."""
    out = {}
    for dtype, std in ((jnp.float32, 0.3), (jnp.bfloat16, BF16_STD)):
        model = toy(dtype=dtype, flash_override=True, init_std=std)
        params = model.init(jax.random.PRNGKey(0))
        with jax.default_matmul_precision("highest"):
            got = on_mesh(model, mesh, jax.value_and_grad(model.loss),
                          (P(), model.partition_specs()))(params, *batch)
        want = jax.value_and_grad(
            lambda p: ref.loss(p, *batch, arch=ARCH))(params)
        out[jnp.dtype(dtype).name] = (got, want)
    return out


LEAVES = sorted(_leaves(jax.eval_shape(toy().init, jax.random.PRNGKey(0))))


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 0.08)])
def test_loss_matches_the_reference(gradients, dtype, tol):
    (got, _), (want, _) = gradients[dtype]
    assert abs(float(got) - float(want)) <= tol


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_matches_the_reference(gradients, leaf):
    """fp32 run: each leaf's gradient to 1e-4 of its largest element.
    bf16 run: within a tenth of it in the root mean square.  The
    embedding's leaf is the head's too: its gradient is the sum of both
    uses, on both sides."""
    for dtype, tol in (("float32", 1e-4), ("bfloat16", 0.1)):
        (_, got), (_, want) = gradients[dtype]
        g = np.asarray(_leaves(got)[leaf], np.float32)
        w = np.asarray(_leaves(want)[leaf], np.float32)
        scale = np.abs(w).max()
        if "router_bias" in leaf:
            # steers the choice only: no gradient, on either side
            assert scale == 0 and np.abs(g).max() == 0
            continue
        assert scale > 0, leaf
        err = (np.abs(g - w).max() if dtype == "float32"
               else np.sqrt(np.mean((g - w) ** 2)))
        assert err <= tol * scale, (leaf, dtype, err, scale)


def test_the_tied_leaf_takes_the_gradient_of_both_uses(mesh, batch):
    """One (V, H) leaf read as embedding and as head: its gradient is
    the embedding's of an untied twin plus that twin's head's."""
    tied, untied = toy(), toy(tie_word_embeddings=False)
    params = tied.init(jax.random.PRNGKey(0))
    assert "head" not in params
    twin = dict(params, head={"weight": params["embed"]["weight"]})
    assert jax.tree.structure(twin) == jax.tree.structure(
        jax.eval_shape(untied.init, jax.random.PRNGKey(0)))
    with jax.default_matmul_precision("highest"):
        one = on_mesh(tied, mesh, jax.grad(tied.loss),
                      tied.partition_specs())(params, *batch)
        two = on_mesh(untied, mesh, jax.grad(untied.loss),
                      untied.partition_specs())(twin, *batch)
    both = two["embed"]["weight"] + two["head"]["weight"]
    assert float(jnp.abs(two["embed"]["weight"]).max()) > 0
    assert float(jnp.abs(two["head"]["weight"]).max()) > 0
    np.testing.assert_allclose(one["embed"]["weight"], both,
                               atol=1e-5 * float(jnp.abs(both).max()))
    specs = tied.partition_specs()
    assert "head" not in specs and specs["embed"]["weight"] == P("tp", None)
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, P)) == jax.tree.structure(params)


def test_the_model_holds_what_its_config_says():
    model = toy()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "final_ln"} | {
        f"block{i}" for i in range(6)}
    for i, kind in enumerate(KINDS):
        attn = shapes[f"block{i}"]["attn"]
        if kind == "conv":
            assert {k: v.shape for k, v in attn.items()} == {
                "in_proj": (32, 96), "conv": (32, 3), "out_proj": (32, 32)}
        else:
            assert {k: v.shape for k, v in _leaves(attn).items()} == {
                "['q']": (32, 32), "['k']": (32, 16), "['v']": (32, 16),
                "['q_norm']['weight']": (8,), "['k_norm']['weight']": (8,),
                "['proj']": (32, 32)}
    assert set(shapes["block1"]["mlp"]) == {"gate_up", "down"}
    mlp = shapes["block2"]["mlp"]
    # the router at its published width, its own experts, nothing shared
    assert {k: v.shape for k, v in mlp.items()} == {
        "router": (32, 16), "router_bias": (16,),
        "experts_gate_up": (8, 32, 16), "experts_down": (8, 8, 32)}
    with pytest.raises(ValueError, match="layer_types"):
        toy(layer_types=("conv", "latent") * 3)
    with pytest.raises(ValueError, match="layer_types"):
        toy(num_layers=5)


# ------------------------- the gated short convolution -------------------------

def _loop_over_time(bcu, w):
    """The definition, a token at a time, in float64 numpy."""
    bcu, w = np.asarray(bcu, np.float64), np.asarray(w, np.float64)
    h, taps = w.shape
    b, c, u = bcu[..., :h], bcu[..., h:2 * h], bcu[..., 2 * h:]
    z = b * u
    out = np.zeros_like(z)
    for t in range(z.shape[1]):
        for j in range(taps):
            s = t - (taps - 1) + j
            if s >= 0:
                out[:, t] += w[:, j] * z[:, s]
    return c * out


@pytest.mark.parametrize("taps", [3, 4])
def test_gated_short_conv_is_the_loop_over_time(taps):
    bcu = jax.random.normal(jax.random.PRNGKey(0), (2, 17, 3 * 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, taps))
    got = jax.jit(gated_short_conv)(bcu, w)
    assert got.shape == (2, 17, 8) and got.dtype == bcu.dtype
    np.testing.assert_allclose(got, _loop_over_time(bcu, w), atol=1e-5)
    # the pullback, against the loop's own by finite differences of a
    # linear functional of the output
    ct = jax.random.normal(jax.random.PRNGKey(2), got.shape)
    d_bcu, d_w = jax.grad(
        lambda x, y: jnp.sum(gated_short_conv(x, y) * ct), (0, 1))(bcu, w)
    eps = 1e-6
    rng = np.random.default_rng(0)
    for _ in range(8):
        dx = rng.standard_normal(bcu.shape)
        dw = rng.standard_normal(w.shape)
        up = _loop_over_time(np.asarray(bcu) + eps * dx,
                             np.asarray(w) + eps * dw)
        down = _loop_over_time(np.asarray(bcu) - eps * dx,
                               np.asarray(w) - eps * dw)
        want = np.sum((up - down) * np.asarray(ct)) / (2 * eps)
        got_d = np.sum(np.asarray(d_bcu) * dx) + np.sum(np.asarray(d_w) * dw)
        np.testing.assert_allclose(got_d, want, rtol=2e-4, atol=1e-4)


def test_gated_short_conv_reads_no_token_after_its_own():
    """Token t's output is unmoved by tokens after t, and moved by
    token t - 2 (the oldest tap is read)."""
    bcu = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 3 * 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 3))
    base = gated_short_conv(bcu, w)
    for t in (0, 5, 11):
        moved = gated_short_conv(bcu.at[:, t + 1:].add(1.0), w)
        np.testing.assert_array_equal(moved[:, :t + 1], base[:, :t + 1])
    moved = gated_short_conv(bcu.at[:, 3].add(1.0), w)
    assert float(jnp.abs(moved[:, 5] - base[:, 5]).max()) > 1e-3
    assert float(jnp.abs(moved[:, 6] - base[:, 6]).max()) == 0
    with pytest.raises(ValueError, match="thirds"):
        gated_short_conv(bcu[..., :-1], w)


def test_gated_short_conv_in_bf16_computes_in_float32():
    bcu = jax.random.normal(jax.random.PRNGKey(0), (2, 33, 3 * 16),
                            jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 3), jnp.bfloat16)
    got = gated_short_conv(bcu, w)
    assert got.dtype == jnp.bfloat16
    want = _loop_over_time(bcu.astype(jnp.float32), w.astype(jnp.float32))
    # one rounding of the result, none inside
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               rtol=2 ** -8, atol=1e-6)


# ------------------------- q's and k's norm and rotation -------------------------

def test_qk_norm_and_rotation_match_the_reference_at_d64():
    """4 query heads a kv head at the published head width: the model's
    head-major operands against the reference's normed, rotate-half
    turned heads."""
    model = ShortConvMoE(ShortConvMoEConfig(
        vocab_size=64, hidden=512, num_heads=8, num_kv_heads=2, head_dim=64,
        intermediate_size=48, moe_intermediate_size=8, n_routed_experts=16,
        experts_count=8))
    arch = ref._Arch.of(dict(ARCH, hidden_size=512, num_attention_heads=8,
                             num_key_value_heads=2, rope_theta=1e6))
    for n, seed in ((8, 0), (2, 1)):
        x = jax.random.normal(jax.random.PRNGKey(seed), (2, 48, n * 64))
        weight = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(7), (64,))
        got = model.normed_turned(x, weight, n)
        want = ref.normed_rotated(x.reshape(2, 48, n, 64),
                                  {"weight": weight}, arch)
        assert got.shape == (2, n, 48, 64)
        np.testing.assert_allclose(got, want.transpose(0, 2, 1, 3),
                                   atol=2e-5)
    # position 0 is not turned, and a turn keeps a head's length
    np.testing.assert_allclose(
        jnp.linalg.norm(got, axis=-1),
        jnp.linalg.norm(want.transpose(0, 2, 1, 3), axis=-1), atol=2e-5)


# ------------------------------ the share ------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer():
    """Both chips of an expert-parallel pair compute their own experts'
    part and nothing is shared: the two parts added up are the uncut
    layer (the case beside `test_mla_moe.py::test_the_shares_add_up_to_
    the_uncut_layer`, which has a shared expert to count once)."""
    h, f, e, k = 32, 8, 32, 4
    whole = HeldExpertsMLP(h, f, e, first=0, count=e, top_k=k, n_shared=0,
                           init_std=0.3, bias_range=0.05)
    params = whole.init(jax.random.PRNGKey(3))
    assert "shared_gate_up" not in params
    x = jax.random.normal(jax.random.PRNGKey(4), (48, h))
    arch = dict(ARCH, num_experts_per_tok=k, experts_first=0, num_experts=e)
    uncut = ref.expert_layer(params, x, arch=arch)
    with jax.default_matmul_precision("highest"):
        routed = jnp.zeros_like(x)
        for first in (0, 16):
            share = HeldExpertsMLP(h, f, e, first=first, count=16, top_k=k,
                                   n_shared=0)
            mine = dict(params,
                        experts_gate_up=params["experts_gate_up"][
                            first:first + 16],
                        experts_down=params["experts_down"][first:first + 16])
            y, stats = share.apply(mine, x)
            assert int(stats.overflow) == 0
            routed = routed + y
            # the reference, given the same share, gives the same part
            np.testing.assert_allclose(
                y, ref.expert_layer(mine, x, arch=dict(
                    arch, experts_first=first, num_experts=16)), atol=2e-5)
        got, stats = whole.apply(params, x)
    assert int(jnp.sum(stats.counts)) == 48 * k     # no token dropped
    np.testing.assert_allclose(routed, uncut, atol=5e-5)
    np.testing.assert_allclose(got, uncut, atol=5e-5)


@pytest.mark.parametrize("tokens", [8, 1000, 8192])
def test_rows_bound_at_half_the_experts_is_every_assignment(tokens):
    """Twice what uniform routing sends half the experts is all there
    is: no assignment can overflow, whatever the router does."""
    layer = HeldExpertsMLP(2048, 1792, 32, first=0, count=16, top_k=4,
                           n_shared=0)
    assert layer.rows_bound(tokens) == tokens * 4
    third = HeldExpertsMLP(2048, 1792, 32, first=0, count=8, top_k=4,
                           n_shared=0)
    assert third.rows_bound(8192) == 8192 * 2


def test_every_token_to_the_held_half_overflows_nothing():
    """The worst router there is: a bias that sends every assignment to
    the held experts."""
    layer = HeldExpertsMLP(32, 8, 32, first=0, count=16, top_k=4, n_shared=0,
                           init_std=0.3)
    params = layer.init(jax.random.PRNGKey(0))
    params["router_bias"] = jnp.where(jnp.arange(32) < 16, 10.0, 0.0)
    _, stats = layer.apply(params, jax.random.normal(
        jax.random.PRNGKey(1), (40, 32)))
    assert int(jnp.sum(stats.counts)) == 160 and int(stats.overflow) == 0


# ------------------- the untied stacks, as they were -------------------

def test_an_untied_stack_has_its_head_and_reads_it(mesh, batch):
    """`tie_word_embeddings` false is the stack the other cells run: a
    head leaf of its own, which the logits read."""
    model = toy(tie_word_embeddings=False)
    params = model.init(jax.random.PRNGKey(0))
    assert params["head"]["weight"].shape == (64, 32)
    assert model.partition_specs()["head"]["weight"] == P("tp", None)
    with jax.default_matmul_precision("highest"):
        got = on_mesh(model, mesh,
                      lambda p, t, l: model.token_losses(p, t, l)[0],
                      P())(params, *batch)
    want = ref.token_losses(params, *batch,
                            arch=dict(ARCH, tie_word_embeddings=False))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)
    zeroed = dict(params, head={"weight": jnp.zeros((64, 32))})
    with jax.default_matmul_precision("highest"):
        flat = on_mesh(model, mesh,
                       lambda p, t, l: model.token_losses(p, t, l)[0],
                       P())(zeroed, *batch)
    np.testing.assert_allclose(flat, np.log(64.0), atol=1e-5)


# ------------------------- the step, named from inside -------------------------

def test_every_instruction_of_the_step_is_owned(mesh, batch):
    """A step of the short-convolution stack through the step builder:
    every instruction that takes time is owned by a name of
    `scopes.OWNERS`, and every sublayer the vocabulary gained for this
    stack is opened."""
    from apex_tpu.monitor import scopes
    from apex_tpu.monitor.comms.hlo import parse_module
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    model = toy(init_std=0.06)
    params = model.init(jax.random.PRNGKey(8))
    opt = FusedAdam(lr=3e-3, use_pallas=False)
    state = init_sharded_optimizer(opt, model, params, mesh)
    # the tied leaf is one buffer of the flat state: Adam over it once
    assert int(state.params.shape[0]) >= sum(
        x.size for x in jax.tree.leaves(params))
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)
    first = None
    for _ in range(6):
        state, loss = step(state, *batch)
        first = float(loss) if first is None else first
    assert np.isfinite(float(loss)) and float(loss) < first

    text = scopes.step_text()
    found = scopes.owners(text)
    (entry,) = [c for c in parse_module(text) if c.is_entry]
    timed = ("fusion", "copy", "custom-call", "convolution", "dot", "sort",
             "scatter", "gather", "while")
    unowned = [i.name for i in entry.instructions
               if i.opcode in timed and found[i.name][0] == scopes.UNOWNED
               and not all(o.startswith("constant")
                           for o in i.operand_names)]
    assert not unowned
    owners = {owner for owner, _, _ in found.values()}
    assert {"embed", "head", "loss", "final_ln", "block0/mlp/gate_up",
            "block1/mlp/down"} | {
        f"block2/attn/{s}" for s in ("qkv", "qknorm_rope", "flash",
                                     "proj")} | {
        f"block{i}/attn/{s}" for i in (0, 1, 3, 4, 5) for s in (
            "in_proj", "shortconv", "out_proj")} | {
        f"block5/mlp/{s}" for s in ("router", "dispatch", "experts",
                                    "combine")} <= owners
    assert not {o for o in owners if o.endswith(("mlp/shared", "attn/conv"))}


def test_the_owners_vocabulary_has_the_new_sublayers():
    from apex_tpu.monitor import scopes

    for name in ("attn/in_proj", "attn/shortconv", "attn/out_proj",
                 "attn/qknorm_rope"):
        assert f"block{{i}}/{name}" in scopes.OWNERS
    assert scopes.owner_of(
        "jit(step)/jvp(block0)/attn/shortconv/mul")[0] \
        == "block0/attn/shortconv"
    assert scopes.owner_of(
        "jit(step)/transpose(jvp(block2))/attn/qknorm_rope/mul")[0] \
        == "block2/attn/qknorm_rope"
    # the delta-rule mixer's staging convolution keeps its own name
    assert scopes.owner_of("jit(step)/jvp(block1)/attn/conv/conv_stage")[0] \
        == "block1/attn/conv"


def test_the_committed_v5e_config_for_gqa_at_64_wide_heads(monkeypatch):
    """The seventh cell's flash call, 32 query heads on 8 kv heads of
    64 over one row of 8,192, finds its measured entry: the single
    pass at (2048, 512) blocks."""
    from apex_tpu import tune
    from apex_tpu.ops import flash_attention as FA
    from apex_tpu.tune import defaults

    key = tune.make_key("flash_sdpa", tune.flash_attrs(
        1, 32, 8192, 8192, 64, "bfloat16", True, hkv=8))
    config = defaults.DEFAULTS["v5e"][key]["config"]
    assert config == {"block_q": 2048, "block_k": 512, "fused_bwd": True}
    asked = []
    monkeypatch.setattr(tune, "tuned",
                        lambda op, attrs: asked.append(attrs) or config)
    shape = FA._kernel_shape(8192, 8192, 64, 64, jnp.bfloat16, True,
                             tuner_key=(1, 32, False, 8))
    assert (shape.bq, shape.bk, shape.fused_bwd) == (2048, 512, True)
    assert asked == [tune.flash_attrs(1, 32, 8192, 8192, 64, "bfloat16",
                                      True, hkv=8)]
