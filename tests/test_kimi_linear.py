"""models.hybrid_moe as Kimi-Linear shapes it (a leading dense layer,
K K M K with latent attention that rotates nothing and compresses no
query, beta in (0, 1)) over rows of packed documents, held to the plain
reference the benchmark keeps (`benchmarks/reference/kimi_linear.py`:
float32 jax.numpy, the delta rule a token at a time with an exact
reset, a dense masked softmax, nothing from apex_tpu) on seeded random
weights at toy sizes; the share test; what the older cells' models
trace to."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu.models.hybrid_moe import HybridMoE, HybridMoEConfig  # noqa: E402
from apex_tpu.moe import HeldExpertsMLP  # noqa: E402
from apex_tpu.moe.layer import swiglu  # noqa: E402
from apex_tpu.ops import rope_stage  # noqa: E402
from apex_tpu.parallel import mesh as M  # noqa: E402
from benchmarks.reference import kimi_linear as ref  # noqa: E402

EOD = 63
SEQ = 64
# the model's keys at toy sizes, the source's spelling: five layers, the
# fourth (4, counted from 1) latent attention over 2 heads of 8 + 4 / 8,
# the others KDA (3 heads of 8); layer 0 dense; experts [4, 12) of 20
ARCH = dict(num_attention_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, kv_lora_rank=16,
            linear_attn_config=dict(num_heads=3, head_dim=8,
                                    short_conv_kernel_size=4,
                                    full_attn_layers=[4, 8],
                                    kda_layers=[1, 2, 3, 5, 6, 7]),
            first_k_dense_replace=1, rms_norm_eps=1e-5, num_hidden_layers=5,
            num_experts_per_token=3, experts_first=4, num_experts=8,
            routed_scaling_factor=2.446, moe_renormalize=True,
            eod_token_id=EOD)


def toy(**overrides):
    return HybridMoE(HybridMoEConfig(**{**dict(
        vocab_size=64, hidden=32, num_layers=5, attention_layers=(3,),
        attention_kind="latent", num_heads=2, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, kda_heads=3,
        kda_head_dim=8, kda_rank=8, allow_neg_eigval=False,
        first_k_dense_replace=1, intermediate_size=48,
        moe_intermediate_size=8, n_routed_experts=20, num_experts_per_tok=3,
        routed_scaling_factor=2.446, experts_first=4, experts_count=8,
        init_std=0.3, scan_chunk=16, eod_token_id=EOD), **overrides}))


@pytest.fixture(scope="module")
def mesh():
    M.destroy_model_parallel()
    yield M.initialize_model_parallel(devices=jax.devices()[:1])
    M.destroy_model_parallel()


def _row(key, ends):
    """A row of ids below EOD with an EOD at every position of `ends`."""
    row = np.array(jax.random.randint(key, (SEQ,), 0, EOD))
    row[list(ends)] = EOD
    return row


@pytest.fixture(scope="module")
def batch():
    """Two packed rows.  Row 0: an EOD at position 0 (a document of the
    EOD alone), documents of 1 and 2 tokens (4; 5-6), a boundary on the
    edge of a chunk of 16 (EOD at 15: token 16 starts one) and beside
    it (EOD at 32: token 33 starts one), and an EOD at S - 1.  Row 1:
    one long document and a short last one, cut by the row's end."""
    k0, k1 = jax.random.split(jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.stack([
        _row(k0, (0, 3, 4, 6, 15, 32, SEQ - 1)), _row(k1, (49,))]))
    return tokens, jnp.roll(tokens, -1, axis=1)


def on_mesh(model, mesh, fn, out_specs):
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(model.partition_specs(), P(), P()),
        out_specs=out_specs, check_vma=False))


def test_the_documents_are_the_references(batch):
    docs = toy().documents(batch[0])
    doc, first = ref.documents(batch[0], EOD)
    np.testing.assert_array_equal(docs.ids, doc)
    np.testing.assert_array_equal(docs.first, first)
    assert np.asarray(docs.first)[0, [0, 1, 4, 5, 7, 16, 33]].all()
    assert int(docs.first[0].sum()) == 7 and int(docs.first[1].sum()) == 2
    # a tap r back is open where the document is r tokens old or more
    age = np.asarray([t - max(s for s in range(t + 1) if first[0, s])
                      for t in range(SEQ)])
    for r, tap in enumerate(docs.taps, 1):
        np.testing.assert_array_equal(tap[0, :, 0], age >= r)
    assert toy(eod_token_id=None).documents(batch[0]) is None


# fp32: one computation up to the order of sums, the chunked algebra
# and the pinned decay of a first token against the exact reset (row 0
# has five boundaries inside its first chunk of 16: the running sum of
# g reaches -150 there, where a float32 resolves 1.5e-5, and a loss of
# 5 nats shows it in its fifth digit: 1.3e-4 on one token); bf16: the
# band the benchmark's check allows a single token at the real sizes
@pytest.mark.parametrize("dtype,flash,std,tol", [
    ("float32", False, 0.3, 3e-4), ("float32", True, 0.3, 3e-4),
    ("bfloat16", True, 0.06, 0.7)])
def test_token_losses_match_the_reference(mesh, batch, dtype, flash, std,
                                          tol):
    model = toy(dtype=jnp.dtype(dtype), flash_override=flash, init_std=std)
    params = model.init(jax.random.PRNGKey(3))
    got, second, stats = on_mesh(
        model, mesh, model.token_losses, (P(), None, P()))(params, *batch)
    want, none = ref.token_losses(params, *batch, arch=ARCH)
    assert second is None and none is None
    assert len(stats) == 4           # the dense layer routes nothing
    assert got.shape == (2, SEQ) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=tol)
    assert sum(int(s.overflow) for s in stats) == 0


def test_a_model_that_forgot_its_boundaries_is_another_function(mesh, batch):
    """The control the benchmark's check rests on: with the EOD an
    ordinary token the reference moves, and the model follows it only
    where it is told no EOD either."""
    model = toy(eod_token_id=None)
    params = model.init(jax.random.PRNGKey(3))
    got = on_mesh(model, mesh, model.token_losses, (P(), None, P()))(
        params, *batch)[0]
    open_, _ = ref.token_losses(params, *batch, arch=ARCH, boundaries=False)
    closed, _ = ref.token_losses(params, *batch, arch=ARCH)
    np.testing.assert_allclose(got, open_, atol=1e-4)
    assert float(jnp.max(jnp.abs(open_ - closed))) > 1e-2
    # up to the first boundary nothing differs: row 1's is at 49
    np.testing.assert_allclose(open_[1, :50], closed[1, :50], atol=1e-5)


@pytest.fixture(scope="module")
def gradients(mesh, batch):
    model = toy(flash_override=True)
    params = model.init(jax.random.PRNGKey(3))
    got = on_mesh(model, mesh, jax.value_and_grad(model.loss),
                  (P(), model.partition_specs()))(params, *batch)
    want = jax.value_and_grad(
        lambda p: ref.loss(p, *batch, arch=ARCH))(params)
    return got, want


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


LATENT = ("q", "kv_a", "kv_a_norm']['weight", "kv_b", "proj")
KDA = ("q", "k", "v", "conv_q", "conv_k", "conv_v", "f_a", "f_b", "a_log",
       "dt_bias", "beta", "g_a", "g_b", "o_norm']['weight", "proj")
LEAVES = ([f"['block3']['attn']['{n}']" for n in LATENT]
          + [f"['block{i}']['attn']['{n}']" for i in (0, 4) for n in KDA]
          + ["['block0']['mlp']['gate_up']", "['block0']['mlp']['down']"]
          + [f"['block2']['mlp']['{n}']" for n in (
              "router", "experts_gate_up", "experts_down", "shared_gate_up",
              "shared_down")]
          + ["['block1']['ln1']['weight']", "['block3']['ln2']['weight']",
             "['embed']['weight']", "['head']['weight']",
             "['final_ln']['weight']"])


def test_loss_matches_the_reference(gradients):
    (loss, _), (want, _) = gradients
    np.testing.assert_allclose(loss, want, rtol=2e-5)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_matches_the_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    got, want = _leaves(got)[leaf], _leaves(want)[leaf]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-4 * scale)


def test_no_leaf_is_left_out_and_the_router_bias_gets_no_gradient(gradients):
    (_, got), (_, want) = gradients
    got, want = _leaves(got), _leaves(want)
    assert set(got) == set(want) and set(LEAVES) <= set(got)
    for name, g in got.items():
        if name.endswith("['router_bias']"):
            assert not np.asarray(g).any()


def test_recomputed_mixers_give_the_same_loss_and_gradients(mesh, batch,
                                                           gradients):
    model = toy(flash_override=True, recompute_mixers=True)
    params = model.init(jax.random.PRNGKey(3))
    loss, grads = on_mesh(model, mesh, jax.value_and_grad(model.loss),
                          (P(), model.partition_specs()))(params, *batch)
    (want_loss, want), _ = gradients
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for name, g in _leaves(grads).items():
        w = _leaves(want)[name]
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * max(
            float(jnp.max(jnp.abs(w))), 1e-6))


# ----------------------- what the checkpoint keeps -----------------------

def _eqns_in(jaxpr):
    """Every equation of a jaxpr and of every jaxpr nested in it."""
    from apex_tpu.monitor import scopes

    for eqn in jaxpr.eqns:
        yield eqn
        for inner in scopes._jaxprs_in(eqn):
            yield from _eqns_in(inner)


def _remat_counts(model, mesh, batch):
    """Of the gradient's jaxpr, nested bodies walked: the forward GEMMs
    by the shapes of activation and weight, the `name` equations, the
    checkpointed mixers, and what `hybrid_moe.stats()` counted meanwhile."""
    from apex_tpu.models import hybrid_moe

    hybrid_moe.reset_stats()
    c = model.c
    fn = shard_map(jax.value_and_grad(model.loss), mesh=mesh,
                   in_specs=(model.partition_specs(), P(), P()),
                   out_specs=(P(), model.partition_specs()), check_vma=False)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    eqns = list(_eqns_in(jax.make_jaxpr(fn)(params, *batch).jaxpr))
    a = batch[0].shape + (c.hidden,)
    gemms = [tuple(v.aval.shape for v in e.invars) for e in eqns
             if e.primitive.name == "dot_general"]
    return {"names": sum(e.primitive.name == "name" for e in eqns),
            # the blocks whose `attn` scope itself holds a checkpoint
            "checkpointed": sorted(
                int(i) for e in eqns if "policy" in e.params
                for i in re.findall(r"jvp\(block(\d+)\)/attn$",
                                    str(e.source_info.name_stack))),
            **hybrid_moe.stats(),
            **{weight: gemms.count((a, (c.hidden, width)))
               for weight, width in (
                   ("kda_qkv", c.kda_heads * c.kda_head_dim),
                   ("beta", c.kda_heads),
                   ("latent_kv_a", c.kv_lora_rank + c.qk_rope_head_dim))}}


@pytest.fixture(scope="module")
def kept(mesh, batch):
    """`_remat_counts` of the toy with `recompute_mixers` under the
    model's policy, under `policy=None` (the parent's checkpoint) and
    under a policy whose names lack `kda_staged`, and of the toy with
    the flag false."""
    from apex_tpu.models import hybrid_moe

    def counts(**config):
        return _remat_counts(toy(**config), mesh, batch)

    found = {"policy": counts(recompute_mixers=True), "flag false": counts()}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hybrid_moe, "_keeps", None)
        found["no policy"] = counts(recompute_mixers=True)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hybrid_moe, "_BY_NAME",
                      jax.checkpoint_policies.save_only_these_names(
                          *set(hybrid_moe.KEPT) - {"kda_staged"}))
        found["a name short"] = counts(recompute_mixers=True)
    return found


# four KDA layers of 3 heads x 8; (2, 64) tokens, float32
@pytest.mark.parametrize("what,with_policy,without", [
    # q, k, v of every KDA layer: the forward's alone, not the
    # recomputation's too; beta's logits
    ("kda_qkv", 4 * 3, 2 * 4 * 3), ("beta", 4, 2 * 4),
    # the eleven tags of a KDA layer, and once more where the
    # recomputation runs them again
    ("names", 4 * 11, 2 * 4 * 11),
    # the latent layer keeps everything: `kv_a` runs once either way,
    # under a checkpoint as every mixer's
    ("latent_kv_a", 1, 1),
    ("checkpointed", [0, 1, 2, 3, 4], [0, 1, 2, 3, 4]),
])
def test_a_kept_activation_is_computed_once(kept, what, with_policy, without):
    assert (kept["policy"][what], kept["no policy"][what]) == (
        with_policy, without)


# q, k, v in front of the convolution and behind it, o and the gated o;
# the two inner activations; the logits: float32, (2, SEQ) tokens
_WIDE = 4 * 2 * SEQ * 3 * 8
_A_LAYER = 8 * _WIDE + 4 * 2 * SEQ * (2 * 8 + 3)


@pytest.mark.parametrize("under,kept_bytes", [
    ("policy", 4 * _A_LAYER),
    # the staged q, k, v are then computed again and count for nothing
    ("a name short", 4 * (_A_LAYER - 3 * _WIDE)),
    # nothing asks the policy: the count says the list did not engage
    ("no policy", 0), ("flag false", 0)])
def test_stats_count_what_the_policy_keeps(kept, under, kept_bytes):
    assert kept[under]["kept_bytes"] == kept_bytes
    assert kept["flag false"]["names"] == 0


def test_the_model_holds_a_dense_layer_kda_and_latent_attention():
    model = toy()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "head", "final_ln"} | {
        f"block{i}" for i in range(5)}
    attn = shapes["block3"]["attn"]
    assert set(attn) == {n.split("'")[0] for n in LATENT}
    assert attn["q"].shape == (32, 2 * 12)       # no compression
    assert attn["kv_a"].shape == (32, 16 + 4)
    assert attn["kv_b"].shape == (16, 2 * 16)
    assert attn["proj"].shape == (2 * 8, 32)
    for i in (0, 1, 2, 4):
        assert set(shapes[f"block{i}"]["attn"]) == {
            n.split("'")[0] for n in KDA}
    assert set(shapes["block0"]["mlp"]) == {"gate_up", "down"}
    assert shapes["block0"]["mlp"]["gate_up"].shape == (32, 96)
    assert shapes["block1"]["mlp"]["router"].shape == (32, 20)
    specs = model.partition_specs()
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, P)) == jax.tree.structure(shapes)


def test_an_unknown_kind_of_attention_is_refused():
    with pytest.raises(ValueError, match="attention_kind"):
        HybridMoE(HybridMoEConfig(attention_kind="sliding"))


def test_beta_stays_under_one(batch):
    model = toy()
    params = model.init(jax.random.PRNGKey(3))["block0"]["attn"]
    a = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 32)) * 4
    beta = model.scan_inputs(params, a, model.documents(batch[0]))[4]
    assert 0 < float(beta.min()) and float(beta.max()) < 1


# ------------------------------ the share ------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Every chip of an expert-parallel group of sixteen computes its
    own experts' part and the shared expert; the routed parts added up,
    with the shared expert counted once, are the uncut reference's
    layer."""
    h, f, e, k, group = 32, 8, 32, 3, 16
    whole = HeldExpertsMLP(h, f, e, first=0, count=e, top_k=k, scale=2.446,
                           init_std=0.3, bias_range=0.05)
    params = whole.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (48, h))
    arch = dict(ARCH, experts_first=0, num_experts=e)
    uncut = ref.expert_layer(params, x, arch=arch)
    held = e // group
    with jax.default_matmul_precision("highest"):
        shared = swiglu(x, params["shared_gate_up"], params["shared_down"])
        routed = jnp.zeros_like(x)
        for first in range(0, e, held):
            share = HeldExpertsMLP(h, f, e, first=first, count=held, top_k=k,
                                   scale=2.446)
            mine = dict(params,
                        experts_gate_up=params["experts_gate_up"][
                            first:first + held],
                        experts_down=params["experts_down"][
                            first:first + held])
            y, stats = share.apply(mine, x)
            assert int(stats.overflow) == 0
            routed = routed + (y - shared)
            np.testing.assert_allclose(
                y, ref.expert_layer(mine, x, arch=dict(
                    arch, experts_first=first, num_experts=held)),
                atol=5e-5)
    np.testing.assert_allclose(routed + shared, uncut, atol=1e-4)


# ---------------------- the staging without a rotation ----------------------

@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "kernels"])
def test_per_head_lanes_are_staged_as_they_are(kernels):
    """q's head-major operand without a rotation: a head's own 64 lanes
    behind its 128, forward and the gradient back, at the cell's head
    geometry over a short row (interpreted blocks under "kernels")."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    nope = jax.random.normal(k1, (1, 256, 4 * 128))
    lanes = jax.random.normal(k2, (1, 256, 4 * 64))
    cot = jax.random.normal(k3, (1, 4, 256, 192))
    out, pull = jax.vjp(lambda n, r: rope_stage.stage_heads(
        n, r, 4, per_head=True, use_pallas_override=kernels), nope, lanes)
    want = jnp.concatenate([nope.reshape(1, 256, 4, 128),
                            lanes.reshape(1, 256, 4, 64)], -1)
    np.testing.assert_array_equal(out, want.transpose(0, 2, 1, 3))
    d_nope, d_lanes = pull(cot)
    back = cot.transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(d_nope, back[..., :128].reshape(1, 256, -1))
    np.testing.assert_array_equal(d_lanes, back[..., 128:].reshape(1, 256, -1))
    text = str(jax.make_jaxpr(lambda n, r: rope_stage.stage_heads(
        n, r, 4, per_head=True, use_pallas_override=kernels))(nope, lanes))
    assert ("name=rope_stage" in text) == kernels


# ------------------ what the older cells' models trace to ------------------

def test_without_an_eod_the_hybrid_stack_knows_no_document(mesh):
    """`eod_token_id` None, the Solar cell's model: no segment ids
    reach the flash call, no mask a tap, no reset the scan, and the
    scope `attn/segments` is never opened; with one, each appears."""
    def text(model):
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        tok = jax.ShapeDtypeStruct((1, 64), jnp.int32)
        fn = shard_map(jax.value_and_grad(model.loss), mesh=mesh,
                       in_specs=(model.partition_specs(), P(), P()),
                       out_specs=(P(), model.partition_specs()),
                       check_vma=False)
        return str(jax.make_jaxpr(fn)(params, tok, tok))

    solar = dict(vocab_size=64, hidden=32, num_layers=4, num_heads=4,
                 num_kv_heads=2, head_dim=8, kda_heads=3, kda_head_dim=8,
                 kda_rank=8, moe_intermediate_size=8, n_routed_experts=20,
                 num_experts_per_tok=3, experts_first=4, experts_count=8,
                 scan_chunk=16)
    plain = text(HybridMoE(HybridMoEConfig(**solar)))
    packed = text(HybridMoE(HybridMoEConfig(eod_token_id=EOD, **solar)))
    # the one cumsum over a row of tokens is the documents'
    over_a_row = "i32[1,64] = cumsum"
    assert over_a_row not in plain and over_a_row in packed
    assert text(HybridMoE(HybridMoEConfig(
        eod_token_id=None, first_k_dense_replace=0, **solar))) == plain
    assert len(packed) > len(plain)


def test_every_instruction_of_the_step_is_owned(mesh, batch):
    """A step of the Kimi-shaped stack over packed rows through the
    step builder: every instruction that takes time is owned by a name
    of `scopes.OWNERS`, and every sublayer the vocabulary gained for
    this stack is opened."""
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
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)
    state, loss = step(state, *batch)
    assert np.isfinite(float(loss))

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
    assert {"block0/attn/segments", "block0/mlp/gate_up",
            "block0/mlp/down"} | {
        f"block3/attn/{s}" for s in ("q", "kv_a", "kv_b", "stage", "flash",
                                     "proj")} | {
        f"block{i}/attn/{s}" for i in (0, 1, 2, 4) for s in (
            "qkv", "conv", "decay", "scan", "onorm", "proj")} | {
        f"block4/mlp/{s}" for s in ("router", "dispatch", "experts",
                                    "shared", "combine")} <= owners


def test_the_owners_vocabulary_has_the_new_sublayers():
    from apex_tpu.monitor import scopes

    for name in ("attn/segments", "attn/q", "attn/stage"):
        assert f"block{{i}}/{name}" in scopes.OWNERS
    assert scopes.owner_of(
        "jit(step)/jvp(block0)/attn/segments/cumsum")[0] \
        == "block0/attn/segments"
    assert scopes.owner_of(
        "jit(step)/transpose(jvp(block3))/attn/stage/rope_unstage")[0] \
        == "block3/attn/stage"
    assert scopes.owner_of("jit(step)/jvp(block3)/attn/q/dot_general")[0] \
        == "block3/attn/q"


# sha256 of str(jax.make_jaxpr(...)) of loss and gradients at the older
# cells' published widths and shapes, addresses scrubbed and the sets a
# jaxpr prints put in order, as the commit before PR 34 (2da4316) traced
# them: PR 34 moved the key-value side of latent attention, the flash
# call, the output projection and the dense SwiGLU into `HeldExpertsLM`,
# gave `stage_heads` a third mode and the hybrid block its documents,
# and the steps of `joyai-llm-flash` and `solar-open2-250b` were to
# compile to what they did.  "kernels" traces the Pallas bodies too
# (interpret mode), "jnp" the references.  PR 35 made the body of the
# hybrid stack's `attn/conv` an op with a Pallas pair of its own
# (`ops/conv_stage.py`): its `jax.numpy` body is the parent's, so
# ("hybrid", "jnp") stands, and ("hybrid", "kernels") stands with that
# one op on its body and differs, by the pair's two names, without.
# PR 38 gave the hybrid's checkpoint a policy and its mixers tags, which
# is another jaxpr under `recompute_mixers` by design (the tests of what
# the checkpoint keeps hold that one): the hybrid's two are traced with
# the flag false since, and their digests are what PR 38's parent
# (c29d385) traced so, where a tag is no equation at all.  To see that
# they are the parent's and not this tree's, run this file's test on a
# checkout of that commit (it reads nothing PR 38 added), 4 passed:
#   mkdir /tmp/c29d385 && git archive c29d385 | tar -x -C /tmp/c29d385
#   cp tests/test_kimi_linear.py /tmp/c29d385/tests/ && cd /tmp/c29d385
#   JAX_PLATFORMS=cpu python -m pytest tests/test_kimi_linear.py \
#       -k parents_jaxpr -p no:cacheprovider
PARENT_JAXPR = {
    ("mla", "jnp"):
        "1a1b2d245e5547abf64ebd8d221234b344b506f3865bdfbfbc60cda68b0110bd",
    ("hybrid", "jnp"):
        "6dd176c3adee121de1d79886c50bed9a4387856473c5111afc2cc7b603bbbda0",
    ("mla", "kernels"):
        "f94e3eb93e0cd8093022f333ab976e27a60126144c20efcebfbb525ab000f824",
    ("hybrid", "kernels"):
        "883aaa0dc350403fee898b66135664f63fc0982cf2951a32992a0b7ac4946772",
}


@pytest.mark.parametrize("which,path", sorted(PARENT_JAXPR))
def test_the_older_cells_models_trace_to_the_parents_jaxpr(mesh, which, path,
                                                           monkeypatch):
    import hashlib
    import re

    from apex_tpu.models import hybrid_moe
    from apex_tpu.models.mla_moe import MLAMoE, MLAMoEConfig

    common = dict(dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
                  flash_override=True if path == "kernels" else None)
    if which == "mla":
        model, shape = MLAMoE(MLAMoEConfig(**common)), (2, 4096)
    else:
        model, shape = HybridMoE(HybridMoEConfig(
            expert_rows_factor=8.0, **common)), (1, 4096)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct(shape, jnp.int32)
    fn = shard_map(jax.value_and_grad(model.loss), mesh=mesh,
                   in_specs=(model.partition_specs(), P(), P()),
                   out_specs=(P(), model.partition_specs()), check_vma=False)

    def digest():
        text = re.sub(r"0x[0-9a-f]+", "0x",
                      str(jax.make_jaxpr(fn)(params, tok, tok)))
        # a frozenset prints in the order of this process's string hashes
        text = re.sub(
            r"frozenset\(\{([^}]*)\}\)",
            lambda m: "frozenset({%s})" % ", ".join(
                sorted(x.strip() for x in m.group(1).split(","))), text)
        return hashlib.sha256(text.encode()).hexdigest(), text

    if (which, path) == ("hybrid", "kernels"):
        # three KDA layers: one call of the pair a layer and direction
        text = digest()[1]
        assert text.count("name=conv_stage") == 3
        assert text.count("name=conv_unstage") == 3
        staged = hybrid_moe.stage_conv_heads
        monkeypatch.setattr(
            hybrid_moe, "stage_conv_heads",
            lambda *a, use_pallas_override=None, **kw: staged(
                *a, use_pallas_override=False, **kw))
        jax.clear_caches()      # the first trace is kept
    assert digest()[0] == PARENT_JAXPR[which, path]


def test_the_committed_v5e_config_for_packed_latent_attention(monkeypatch):
    """The sixth cell's flash call, one row of 8,192 packed tokens at
    keys 192 / values 128 with segment ids, finds its measured entry:
    two kernels at square blocks (no single pass above (512, 512) fits
    VMEM there)."""
    from apex_tpu import tune
    from apex_tpu.ops import flash_attention as FA
    from apex_tpu.tune import defaults

    key = tune.make_key("flash_sdpa", tune.flash_attrs(
        1, 32, 8192, 8192, 192, "bfloat16", True, seg=True, dv=128))
    config = defaults.DEFAULTS["v5e"][key]["config"]
    assert config == {"block_q": 1024, "block_k": 1024, "fused_bwd": False}
    asked = []
    monkeypatch.setattr(tune, "tuned",
                        lambda op, attrs: asked.append(attrs) or config)
    shape = FA._kernel_shape(8192, 8192, 192, 128, jnp.bfloat16, True,
                             tuner_key=(1, 32, True))
    assert (shape.bq, shape.bk, shape.fused_bwd) == (1024, 1024, False)
    assert asked == [tune.flash_attrs(1, 32, 8192, 8192, 192, "bfloat16",
                                      True, seg=True, dv=128)]
