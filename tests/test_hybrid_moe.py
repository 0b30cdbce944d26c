"""models.hybrid_moe held to the plain reference the benchmark keeps
(`benchmarks/reference/solar_open2.py`: float32 jax.numpy, the delta
rule a token at a time, nothing from apex_tpu) on seeded random weights
at toy sizes; the share test; the router and the grouping at a width
that is no multiple of 128; the step's owners."""

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
from apex_tpu.moe import HeldExpertsMLP, dispatch as D  # noqa: E402
from apex_tpu.moe.layer import swiglu  # noqa: E402
from apex_tpu.moe.router import sigmoid_topk_gates  # noqa: E402
from apex_tpu.parallel import mesh as M  # noqa: E402
from benchmarks.reference import solar_open2 as ref  # noqa: E402

# the model's keys at toy sizes: one period, layer 0 attends (4 query
# heads on 2 kv heads), layers 1-3 KDA (3 heads); experts [4, 12) of 20
# held; 3 a token
ARCH = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            linear_attn_config=dict(num_heads=3, head_dim=8,
                                    short_conv_kernel_size=4),
            kda_allow_neg_eigval=True, gqa_layers=[0, 4],
            rms_norm_eps=1e-5, num_hidden_layers=4, num_experts_per_tok=3,
            experts_first=4, n_routed_experts=8, routed_scaling_factor=1.0,
            norm_topk_prob=True)


def toy(**overrides):
    return HybridMoE(HybridMoEConfig(**{**dict(
        vocab_size=64, hidden=32, num_layers=4, attention_layers=(0,),
        num_heads=4, num_kv_heads=2, head_dim=8, kda_heads=3,
        kda_head_dim=8, kda_rank=8, moe_intermediate_size=8,
        n_routed_experts=20, num_experts_per_tok=3, experts_first=4,
        experts_count=8, init_std=0.3, scan_chunk=16), **overrides}))


@pytest.fixture(scope="module")
def mesh():
    M.destroy_model_parallel()
    yield M.initialize_model_parallel(devices=jax.devices()[:1])
    M.destroy_model_parallel()


@pytest.fixture(scope="module")
def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)
    return tokens, jnp.roll(tokens, -1, axis=1)


def on_mesh(model, mesh, fn, out_specs):
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(model.partition_specs(), P(), P()),
        out_specs=out_specs, check_vma=False))


# fp32: the two are one computation up to the order of sums (and the
# chunked algebra against the recurrence), on weights large enough (std
# 0.3) that a 32-wide model is no flat function.  bf16: the weights of
# the benchmark's rehearsal (std 0.06) and the band the benchmark's
# check allows a single token at the real sizes
@pytest.mark.parametrize("dtype,flash,std,tol", [
    ("float32", False, 0.3, 1e-4), ("float32", True, 0.3, 1e-4),
    ("bfloat16", True, 0.06, 0.7)])
def test_token_losses_match_the_reference(mesh, batch, dtype, flash, std,
                                          tol):
    model = toy(dtype=jnp.dtype(dtype), flash_override=flash, init_std=std)
    params = model.init(jax.random.PRNGKey(3))
    got, second, stats = on_mesh(
        model, mesh, model.token_losses, (P(), None, P()))(params, *batch)
    want, none = ref.token_losses(params, *batch, arch=ARCH)
    assert second is None and none is None and len(stats) == 4
    assert got.shape == (2, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=tol)
    assert sum(int(s.overflow) for s in stats) == 0


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


ATTN = ("q", "k", "v", "gate", "proj")
KDA = ("q", "k", "v", "conv_q", "conv_k", "conv_v", "f_a", "f_b", "a_log",
       "dt_bias", "beta", "g_a", "g_b", "o_norm']['weight", "proj")
LEAVES = ([f"['block0']['attn']['{n}']" for n in ATTN]
          + [f"['block{i}']['attn']['{n}']" for i in (1, 3) for n in KDA]
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


def test_the_router_bias_gets_no_gradient_and_no_leaf_is_left_out(gradients):
    (_, got), (_, want) = gradients
    got, want = _leaves(got), _leaves(want)
    assert set(got) == set(want) and set(LEAVES) <= set(got)
    for name, g in got.items():
        if name.endswith("['router_bias']"):
            assert not np.asarray(g).any()
        else:
            scale = float(jnp.max(jnp.abs(want[name])))
            np.testing.assert_allclose(g, want[name], rtol=2e-4,
                                       atol=1e-4 * max(scale, 1e-6))


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
                   ("rank", c.kda_rank), ("beta", c.kda_heads),
                   ("attn_kv", c.num_kv_heads * c.head_dim))}}


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


# three KDA layers of 3 heads x 8, rank 8; (2, 64) tokens, float32
@pytest.mark.parametrize("what,with_policy,without", [
    # q, k, v of every KDA layer: the forward's alone, not the
    # recomputation's too
    ("kda_qkv", 3 * 3, 2 * 3 * 3),
    # `f_a` and `g_a`; beta's logits
    ("rank", 3 * 2, 2 * 3 * 2), ("beta", 3, 2 * 3),
    # the eleven tags of a KDA layer, and once more where the
    # recomputation runs them again
    ("names", 3 * 11, 2 * 3 * 11),
    # the layer that attends keeps everything: its k and v projections
    # run once either way, under a checkpoint as every mixer's
    ("attn_kv", 2, 2), ("checkpointed", [0, 1, 2, 3], [0, 1, 2, 3]),
])
def test_a_kept_activation_is_computed_once(kept, what, with_policy, without):
    assert (kept["policy"][what], kept["no policy"][what]) == (
        with_policy, without)


# q, k, v in front of the convolution and behind it, o and the gated o;
# the two inner activations; the logits: float32, (2, 64) tokens
_WIDE = 4 * 2 * 64 * 3 * 8
_A_LAYER = 8 * _WIDE + 4 * 2 * 64 * (2 * 8 + 3)


@pytest.mark.parametrize("under,kept_bytes", [
    ("policy", 3 * _A_LAYER),
    # the staged q, k, v are then computed again and count for nothing
    ("a name short", 3 * (_A_LAYER - 3 * _WIDE)),
    # nothing asks the policy: the count says the list did not engage
    ("no policy", 0), ("flag false", 0)])
def test_stats_count_what_the_policy_keeps(kept, under, kept_bytes):
    assert kept[under]["kept_bytes"] == kept_bytes
    assert kept["flag false"]["names"] == 0


def test_the_model_holds_two_kinds_of_layer_and_its_own_experts():
    model = toy()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "head", "final_ln", "block0", "block1",
                           "block2", "block3"}
    assert set(shapes["block0"]["attn"]) == set(ATTN)
    assert shapes["block0"]["attn"]["k"].shape == (32, 2 * 8)
    assert shapes["block0"]["attn"]["gate"].shape == (32, 4 * 8)
    for i in (1, 2, 3):
        attn = shapes[f"block{i}"]["attn"]
        assert set(attn) == {n.split("'")[0] for n in KDA}
        assert attn["conv_k"].shape == (4, 24)
        assert attn["f_a"].shape == (32, 8) and attn["f_b"].shape == (8, 24)
        assert attn["a_log"].shape == (3,) and attn["beta"].shape == (32, 3)
        assert attn["o_norm"]["weight"].shape == (8,)
    mlp = shapes["block0"]["mlp"]    # every layer is an expert layer
    assert mlp["experts_gate_up"].shape == (8, 32, 16)
    assert mlp["router"].shape == (32, 20)       # the published width
    specs = model.partition_specs()
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, P)) == jax.tree.structure(shapes)


def test_the_decay_starts_as_the_papers_code_starts_it():
    params = toy().init(jax.random.PRNGKey(5))["block1"]["attn"]
    rate = np.exp(np.asarray(params["a_log"]))
    step = np.log1p(np.exp(np.asarray(params["dt_bias"])))    # softplus
    assert ((1 <= rate) & (rate <= 16)).all()
    assert ((0.999e-3 <= step) & (step <= 1.001e-1)).all()


def test_routing_counts_are_the_expert_layers(mesh, batch):
    model = toy()
    params = model.init(jax.random.PRNGKey(3))
    counts, overflow = jax.jit(shard_map(
        lambda p, t: model.routing_counts(p, t), mesh=mesh,
        in_specs=(model.partition_specs(), P()), out_specs=(P(), P()),
        check_vma=False))(params, batch[0])
    assert counts.shape == (4, 8) and overflow.shape == (4,)
    assert int(counts.sum()) > 0 and not np.asarray(overflow).any()


# ------------------------------ the share ------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Every chip of an expert-parallel group of five computes its own
    experts' part and the shared expert; the routed parts added up,
    with the shared expert counted once, are the uncut layer."""
    h, f, e, k = 32, 8, 20, 3
    whole = HeldExpertsMLP(h, f, e, first=0, count=e, top_k=k, scale=1.0,
                           init_std=0.3, bias_range=0.05)
    params = whole.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (48, h))
    arch = dict(ARCH, experts_first=0, n_routed_experts=e)
    uncut = ref.expert_layer(params, x, arch=arch)
    with jax.default_matmul_precision("highest"):
        shared = swiglu(x, params["shared_gate_up"], params["shared_down"])
        routed = jnp.zeros_like(x)
        for first in range(0, e, 4):
            share = HeldExpertsMLP(h, f, e, first=first, count=4, top_k=k,
                                   scale=1.0)
            mine = dict(params,
                        experts_gate_up=params["experts_gate_up"][
                            first:first + 4],
                        experts_down=params["experts_down"][first:first + 4])
            y, stats = share.apply(mine, x)
            assert int(stats.overflow) == 0
            routed = routed + (y - shared)
            # the reference, given the same share, gives the same part
            np.testing.assert_allclose(
                y, ref.expert_layer(mine, x, arch=dict(
                    arch, experts_first=first, n_routed_experts=4)),
                atol=2e-5)
        got, _ = whole.apply(params, x)
    np.testing.assert_allclose(routed + shared, uncut, atol=5e-5)
    np.testing.assert_allclose(got, uncut, atol=5e-5)


# ------------- the router and the grouping, 2.5 lane tiles wide -------------

@pytest.fixture(scope="module")
def routed_320():
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(ks[0], (96, 32))
    wr = jax.random.normal(ks[1], (32, 320)) * 0.3
    bias = jax.random.uniform(ks[2], (320,), minval=-0.05, maxval=0.05)
    return x, wr, bias, sigmoid_topk_gates(x, wr, bias, 8)


def test_the_router_at_320_wide_chooses_the_eight_largest(routed_320):
    x, wr, bias, gates = routed_320
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.sigmoid(x @ wr))
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1,
                      kind="stable")[:, :8]
    assert gates.scores.shape == (96, 320) and gates.idx.shape == (96, 8)
    np.testing.assert_array_equal(np.sort(gates.idx, -1), np.sort(want, -1))
    chosen = np.take_along_axis(scores, np.asarray(gates.idx), -1)
    np.testing.assert_allclose(gates.weight, chosen / chosen.sum(
        -1, keepdims=True), rtol=1e-5)
    assert int(gates.idx.max()) > 256      # the last half tile is reached


@pytest.mark.parametrize("first,count", [(0, 8), (312, 8), (250, 13)])
def test_grouping_at_320_wide_sorts_the_held_assignments(routed_320, first,
                                                        count):
    x, _, _, gates = routed_320
    idx = np.asarray(gates.idx)
    held = (idx >= first) & (idx < first + count)
    groups = D.group_by_expert(gates.idx, first, count, 96 * 8)
    np.testing.assert_array_equal(
        groups.counts, [(idx == first + e).sum() for e in range(count)])
    assert int(groups.overflow) == 0
    assert int(groups.valid.sum()) == held.sum() == int(groups.sizes.sum())
    # gathered and scattered back with weight 1: every token comes back
    # as many times as it chose a held expert
    y = D.scatter_groups(D.gather_groups(x, groups).astype(jnp.float32),
                         jnp.ones_like(gates.weight), groups, 96)
    np.testing.assert_allclose(y, x * held.sum(-1, keepdims=True), rtol=1e-6)


def test_the_held_layer_at_320_by_1280_over_8(mesh):
    """The benchmark's expert layer at its published router width and
    expert width, 8 held of 320, on a few tokens: against the reference
    given the same share."""
    layer = HeldExpertsMLP(64, 1280, 320, first=0, count=8, top_k=8,
                           init_std=0.1, bias_range=0.05)
    params = layer.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (256, 64))
    assert layer.rows_bound(256) == 104      # 2 x 256 x 8 x 8 / 320, to 8
    with jax.default_matmul_precision("highest"):
        y, stats = layer.apply(params, x)
    assert int(stats.overflow) == 0 and int(stats.counts.sum()) > 0
    want = ref.expert_layer(params, x, arch=dict(
        ARCH, num_experts_per_tok=8, experts_first=0, n_routed_experts=8))
    np.testing.assert_allclose(y, want, atol=2e-4, rtol=1e-4)


# ------------------------------- the scopes -------------------------------

def test_every_instruction_of_the_step_is_owned(mesh):
    """A step of the hybrid through the step builder: every GEMM, every
    loop of the scan and every fusion that takes time is owned by a name
    of `scopes.OWNERS`, and every sublayer the vocabulary gained for the
    block is opened."""
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
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
    state, loss = step(state, tokens, jnp.roll(tokens, -1, axis=1))
    assert np.isfinite(float(loss))

    text = scopes.step_text()
    found = scopes.owners(text)
    (entry,) = [c for c in parse_module(text) if c.is_entry]
    timed = ("fusion", "copy", "custom-call", "convolution", "dot", "sort",
             "scatter", "gather", "while")
    # but a copy of a scalar constant that sublayers of every kind share
    unowned = [i.name for i in entry.instructions
               if i.opcode in timed and found[i.name][0] == scopes.UNOWNED
               and not all(o.startswith("constant")
                           for o in i.operand_names)]
    assert not unowned
    owners = {owner for owner, _, _ in found.values()}
    assert {f"block0/attn/{s}" for s in ("qkv", "flash", "gate", "proj")} | {
        f"block{i}/attn/{s}" for i in (1, 2, 3) for s in (
            "qkv", "conv", "decay", "scan", "onorm", "proj")} | {
        f"block3/mlp/{s}" for s in ("router", "dispatch", "experts",
                                    "shared", "combine")} <= owners
    loops = [i for i in entry.instructions if i.opcode == "while"]
    assert loops and all(
        found[i.name][0].endswith("/attn/scan") for i in loops)
    # and each is a path the vocabulary spells
    assert owners - {scopes.UNOWNED} <= {
        p.replace("{i}", str(i)) for p in scopes.OWNERS for i in range(4)}


def test_the_steps_recomputation_is_the_kda_mixers_without_their_gemms(mesh):
    """A step of the hybrid with `recompute_mixers` through the step
    builder: what `scopes.step_rematted` names runs in the backward
    under a KDA layer's convolution, decay, scan or output norm; no
    projection over the hidden width and nothing of the layer that
    attends is among it."""
    from apex_tpu.monitor import scopes
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    model = toy(init_std=0.06, recompute_mixers=True)
    opt = FusedAdam(lr=3e-3, use_pallas=False)
    state = init_sharded_optimizer(
        opt, model, model.init(jax.random.PRNGKey(8)), mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
    step(state, tokens, jnp.roll(tokens, -1, axis=1))

    found, again = scopes.step_owners(), scopes.step_rematted()
    assert again <= set(found)
    assert {found[n][:2] for n in again} == {
        (f"block{i}/attn/{s}", "bwd") for i in (1, 2, 3)
        for s in ("conv", "decay", "scan", "onorm")}


def test_a_wider_row_bound_changes_the_buffer_and_not_the_numbers():
    """`rows_factor` sizes the grouped buffer (whole sublane tiles,
    never more than every assignment); where nothing overflows the
    narrower one, the two compute the same."""
    kw = dict(first=4, count=8, top_k=3, init_std=0.3, bias_range=0.05)
    plain = HeldExpertsMLP(32, 8, 20, **kw)
    wide = HeldExpertsMLP(32, 8, 20, rows_factor=8.0, **kw)
    assert plain.rows_bound(4096) == 9832
    assert wide.rows_bound(4096) == 3 * 4096       # every assignment
    assert wide.rows_bound(128) == 384 and plain.rows_bound(128) == 312
    params = plain.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (128, 32))
    (a, sa), (b, sb) = plain.apply(params, x), wide.apply(params, x)
    assert int(sa.overflow) == 0 and int(sb.overflow) == 0
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(sa.counts, sb.counts)
