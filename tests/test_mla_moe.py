"""models.mla_moe and the pieces of apex_tpu.moe it is built on, held to
the plain reference the benchmark keeps (`benchmarks/reference/
joyai_llm_flash.py`: float32 jax.numpy, nothing from apex_tpu) on seeded
random weights at toy sizes."""

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

from apex_tpu.models.mla_moe import MLAMoE, MLAMoEConfig  # noqa: E402
from apex_tpu.moe import HeldExpertsMLP, dispatch as D  # noqa: E402
from apex_tpu.moe.layer import swiglu  # noqa: E402
from apex_tpu.moe.router import sigmoid_topk_gates  # noqa: E402
from apex_tpu.ops import rope_stage  # noqa: E402
from apex_tpu.parallel import mesh as M  # noqa: E402
from benchmarks.reference import joyai_llm_flash as ref  # noqa: E402

# the family's keys at toy sizes: one dense layer, two expert layers
# and the MTP module; experts [4, 12) of 16 held; 3 a token
ARCH = dict(num_attention_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, kv_lora_rank=16, rope_theta=1e4, rms_norm_eps=1e-6,
            num_hidden_layers=3, first_k_dense_replace=1,
            num_nextn_predict_layers=1, num_experts_per_tok=3,
            experts_first=4, n_routed_experts=8, routed_scaling_factor=2.5,
            norm_topk_prob=True, mtp_loss_weight=0.3)


def toy(**overrides):
    return MLAMoE(MLAMoEConfig(
        vocab_size=64, hidden=32, num_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=48, moe_intermediate_size=8,
        n_routed_experts=16, num_experts_per_tok=3, first_k_dense_replace=1,
        num_expert_layers=2, experts_first=4, experts_count=8,
        rope_theta=1e4, **{"init_std": 0.3, **overrides}))


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


# fp32: the two are one computation up to the order of sums, on
# weights large enough (std 0.3) that a 32-wide model is no flat
# function.  bf16: every activation is rounded to 8 bits of mantissa and
# a near-tie in a router can go the other way, so the weights are those
# of the benchmark's rehearsal (std 0.06) and the band is what the
# benchmark's check allows a single token at the real sizes
BF16_STD = 0.06


@pytest.mark.parametrize("dtype,flash,std,tol", [
    (jnp.float32, None, 0.3, 2e-5), (jnp.float32, True, 0.3, 2e-5),
    (jnp.bfloat16, None, BF16_STD, 0.25)])
@pytest.mark.parametrize("head", ["main", "mtp"])
def test_token_losses_match_the_reference(mesh, batch, dtype, flash, std,
                                          tol, head):
    model = toy(dtype=dtype, flash_override=flash, init_std=std)
    params = model.init(jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        got = on_mesh(model, mesh,
                      lambda p, t, l: model.token_losses(p, t, l)[:2],
                      (P(), P()))(params, *batch)
    want = ref.token_losses(params, *batch, arch=ARCH)
    i = ("main", "mtp").index(head)
    assert got[i].shape == (2, 32) and got[i].dtype == jnp.float32
    np.testing.assert_allclose(got[i], want[i], atol=tol, rtol=0)
    if dtype == jnp.bfloat16:      # and far closer than the band on the whole
        assert float(jnp.sqrt(jnp.mean((got[i] - want[i]) ** 2))) < 0.04


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


def _leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


LEAVES = sorted(_leaves(jax.eval_shape(toy().init, jax.random.PRNGKey(0))))


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 0.08)])
def test_loss_matches_the_reference(gradients, dtype, tol):
    (got, _), (want, _) = gradients[dtype]
    assert abs(float(got) - float(want)) <= tol


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_matches_the_reference(gradients, leaf):
    """fp32 run: each leaf's gradient to 1e-4 of its largest element.
    bf16 run: within a tenth of it in the root mean square (the
    stated band: bf16 rounds every activation and gradient of the
    chain; the fp32 case is the one that pins the arithmetic)."""
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


def test_the_model_holds_parameters_for_its_own_experts_only():
    model = toy()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mlp = shapes["block1"]["mlp"]
    assert mlp["experts_gate_up"].shape == (8, 32, 16)
    assert mlp["experts_down"].shape == (8, 8, 32)
    assert mlp["router"].shape == (32, 16)       # the published width
    assert mlp["router_bias"].shape == (16,)
    assert "router" not in shapes["block0"]["mlp"]   # the leading dense layer
    assert set(shapes) == {"embed", "head", "final_ln", "mtp", "block0",
                           "block1", "block2", "block3"}
    specs = model.partition_specs()
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, P)) == jax.tree.structure(shapes)


# ------------------------------ the share ------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Every chip of an expert-parallel group of four computes its own
    experts' part and the shared expert; the routed parts added up,
    with the shared expert counted once, are the uncut layer."""
    h, f, e, k = 32, 8, 16, 3
    whole = HeldExpertsMLP(h, f, e, first=0, count=e, top_k=k, scale=2.5,
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
                                   scale=2.5)
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


def test_rows_past_the_last_group_reach_nothing(monkeypatch):
    """On the chip a grouped GEMM leaves the rows that belong to no
    group as it finds them, forward and backward (chip_smoke.py's
    `moe_held_experts` found the layer's input gradient off by 3.7
    before the layer zeroed them).  Off the chip `ragged_dot` writes
    zeros there, so the chip is played by a `ragged_dot` that fills
    those rows with rubbish in both directions: the layer's output and
    every gradient must not notice."""
    from apex_tpu.moe import layer as layer_module

    real = jax.lax.ragged_dot       # the patch below replaces jax.lax's own

    def rubbish(rows, sizes):
        past = jnp.arange(rows.shape[0])[:, None] >= jnp.sum(sizes)
        return jnp.where(past, 1e3, rows)

    @jax.custom_vjp
    def chip_ragged_dot(lhs, rhs, sizes):
        return rubbish(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return chip_ragged_dot(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return rubbish(d_lhs, sizes), d_rhs, None

    chip_ragged_dot.defvjp(fwd, bwd)

    layer = HeldExpertsMLP(32, 8, 16, first=4, count=8, top_k=3, scale=2.5,
                           init_std=0.3, bias_range=0.05)
    params = layer.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (48, 32))
    dy = jax.random.normal(jax.random.PRNGKey(5), (48, 32))

    def run():
        y, vjp = jax.vjp(lambda p, x: layer.apply(p, x)[0], params, x)
        return (y,) + vjp(dy)

    with jax.default_matmul_precision("highest"):
        want = run()
        monkeypatch.setattr(
            layer_module.lax, "ragged_dot",
            lambda a, b, sizes, preferred_element_type=None:
            chip_ragged_dot(a, b, sizes))
        got = run()
    assert int(layer.apply(params, x)[1].counts.sum()) < layer.rows_bound(48)
    jax.tree.map(lambda g, w: np.testing.assert_allclose(
        g, w, atol=1e-5, rtol=1e-5), got, want)


# ------------------------------ the router ------------------------------

@pytest.fixture(scope="module")
def routed():
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 32))
    wr = jax.random.normal(jax.random.PRNGKey(6), (32, 16)) * 0.3
    return x, wr


@pytest.mark.parametrize("scale,renormalize", [(2.5, True), (1.0, True),
                                               (2.5, False)])
def test_router_weights_are_the_chosen_scores(routed, scale, renormalize):
    x, wr = routed
    out = sigmoid_topk_gates(x, wr, jnp.zeros((16,)), 3, scale=scale,
                             renormalize=renormalize)
    assert out.scores.dtype == out.weight.dtype == jnp.float32
    chosen = jnp.take_along_axis(out.scores, out.idx, axis=-1)
    if renormalize:    # the chosen experts' weights sum to the scale
        np.testing.assert_allclose(out.weight.sum(-1), scale, rtol=1e-6)
        want = scale * chosen / chosen.sum(-1, keepdims=True)
    else:
        want = scale * chosen
    np.testing.assert_allclose(out.weight, want, rtol=1e-6)
    # without a bias the choice is the three largest scores
    np.testing.assert_array_equal(out.idx, jax.lax.top_k(out.scores, 3)[1])


def test_router_bias_moves_the_choice_and_never_the_weights(routed):
    x, wr = routed
    plain = sigmoid_topk_gates(x, wr, jnp.zeros((16,)), 3, scale=2.5)
    bias = jnp.zeros((16,)).at[11].set(1.0)     # a sigmoid is under 1
    biased = sigmoid_topk_gates(x, wr, bias, 3, scale=2.5)
    assert bool((biased.idx[:, 0] == 11).all())
    assert not bool((plain.idx == 11).any(-1).all())
    np.testing.assert_array_equal(plain.scores, biased.scores)
    chosen = jnp.take_along_axis(biased.scores, biased.idx, axis=-1)
    np.testing.assert_allclose(
        biased.weight, 2.5 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-6)
    # and it is no trained parameter: no gradient reaches it
    g = jax.grad(lambda b: sigmoid_topk_gates(x, wr, b, 3).weight.sum())(bias)
    assert float(jnp.abs(g).max()) == 0.0


def test_router_scores_are_fp32_from_bf16_activations(routed):
    x, wr = routed
    out = sigmoid_topk_gates(x.astype(jnp.bfloat16), wr.astype(jnp.bfloat16),
                             jnp.zeros((16,), jnp.bfloat16), 3)
    assert out.scores.dtype == out.weight.dtype == jnp.float32
    assert out.idx.dtype == jnp.int32


# ------------------------------ the grouping ------------------------------

IDX = jnp.array([[3, 7, 0], [5, 4, 9], [4, 1, 2], [4, 5, 6], [11, 4, 8]])


def test_grouping_sorts_the_held_assignments_by_expert():
    """Experts [4, 8) of uneven load, expert 6 with one token and 7
    with one, experts 0-3 and 8-11 held elsewhere."""
    g = D.group_by_expert(IDX, 4, 4, 8)
    np.testing.assert_array_equal(g.counts, [4, 2, 1, 1])
    np.testing.assert_array_equal(g.sizes, [4, 2, 1, 1])
    assert int(g.overflow) == 0
    np.testing.assert_array_equal(g.valid, [True] * 8)
    np.testing.assert_array_equal(g.token, [1, 2, 3, 4, 1, 3, 3, 0])
    np.testing.assert_array_equal(
        IDX[g.token, g.slot], [4, 4, 4, 4, 5, 5, 6, 7])


def test_grouping_with_an_expert_no_token_chose():
    g = D.group_by_expert(IDX, 8, 4, 8)           # experts 8, 9, 10, 11
    np.testing.assert_array_equal(g.sizes, [1, 1, 0, 1])
    np.testing.assert_array_equal(g.valid, [True] * 3 + [False] * 5)
    np.testing.assert_array_equal(IDX[g.token[:3], g.slot[:3]], [8, 9, 11])


def test_grouping_counts_what_is_beyond_the_bound():
    g = D.group_by_expert(IDX, 4, 4, 5)
    np.testing.assert_array_equal(g.counts, [4, 2, 1, 1])
    np.testing.assert_array_equal(g.sizes, [4, 1, 0, 0])
    assert int(g.overflow) == 3 and int(g.sizes.sum()) == 5


@pytest.mark.parametrize("first,count,rows", [(4, 4, 8), (8, 4, 8),
                                              (0, 12, 16), (4, 4, 5)])
def test_gather_then_weighted_scatter_is_the_weighted_sum(first, count, rows):
    """The round trip: with the identity in place of the experts, every
    token gets itself back times the weights of its held assignments
    that found a row."""
    x = jax.random.normal(jax.random.PRNGKey(7), (5, 6))
    weight = jax.random.uniform(jax.random.PRNGKey(8), (5, 3))
    g = D.group_by_expert(IDX, first, count, rows)
    got = D.scatter_groups(D.gather_groups(x, g), weight, g, 5)
    kept = np.zeros((5, 3), bool)
    kept[np.asarray(g.token)[np.asarray(g.valid)],
         np.asarray(g.slot)[np.asarray(g.valid)]] = True
    held = (IDX >= first) & (IDX < first + count)
    assert kept.sum() == min(int(held.sum()), rows)
    assert not (kept & ~np.asarray(held)).any()
    want = x * jnp.sum(jnp.where(kept, weight, 0.0), -1, keepdims=True)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_the_row_bound_is_twice_the_uniform_expectation():
    layer = HeldExpertsMLP(2048, 768, 256, first=0, count=16, top_k=8)
    assert layer.rows_bound(8192) == 2 * 8192 * 8 * 16 // 256 == 8192
    # never more than every assignment
    small = HeldExpertsMLP(8, 8, 4, first=0, count=4, top_k=2)
    assert small.rows_bound(10) == 20


# ------------------------------ the rotary embedding ------------------------------

@pytest.mark.parametrize("d,heads", [(64, 32), (4, 2), (64, 1)])
def test_interleaved_rope_is_the_complex_rotation(d, heads):
    """The model turns rotary lanes in halves order (`rope_stage`: the
    partners of a pair d/2 lanes apart).  On lanes taken from the
    published pairs (2i, 2i+1) by `halves` that is the published
    rotation, the complex one, of the same lanes."""
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, heads, d))
    cos, sin = rope_stage.rope_tables(16, d, 32e6)
    got = rope_stage.turn_halves(rope_stage.halves(x), cos[:, None],
                                 sin[:, None])
    pairs = np.asarray(x, np.float64).reshape(2, 16, heads, d // 2, 2)
    z = pairs[..., 0] + 1j * pairs[..., 1]
    angle = (np.arange(16)[:, None]
             * 32e6 ** (-np.arange(0, d, 2) / d))[None, :, None, :]
    z = z * np.exp(1j * angle)
    want = np.concatenate([z.real, z.imag], -1)     # halves of the pairs
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got, rope_stage.halves(ref._rope(x, 32e6)),
                               atol=2e-5)
    # position 0 is left as it is
    np.testing.assert_array_equal(got[:, 0], rope_stage.halves(x)[:, 0])


# ------------------------------ the attention alone ------------------------------

# `_attention` against the published-order formulation (the reference's:
# interleaved pairs turned as complex numbers, k's rotary row broadcast
# to the heads, one GEMM a projection), float32, on the same `params`:
# the model re-orders rotary columns and splits GEMMs inside the step,
# and every gradient must come back in the published order.  "toy" is
# the file's small model; "cell4" has the benchmark cell's head
# geometry (32 heads, keys 128 + 64, values 128) over a short sequence,
# where the staging pass runs in blocks (interpreted) under "kernels"
GEOMETRY = {
    "toy": dict(num_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=4,
                v_head_dim=8, seq=32, batch=2),
    "cell4": dict(num_heads=32, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128, seq=128, batch=1),
}
ATTN_LEAVES = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "proj")


def _attention_pair(geometry, flash):
    g = dict(GEOMETRY[geometry])
    seq, batch = g.pop("seq"), g.pop("batch")
    model = MLAMoE(MLAMoEConfig(
        vocab_size=64, hidden=32, q_lora_rank=24, kv_lora_rank=16,
        rope_theta=1e4, init_std=0.3, flash_override=flash, **g))
    p = model._init_block(jax.random.PRNGKey(2), 0)["attn"]
    a, dy = jax.random.normal(jax.random.PRNGKey(3), (2, batch, seq, 32))
    arch = ref._Arch.of(dict(
        ARCH, num_attention_heads=g["num_heads"],
        qk_nope_head_dim=g["qk_nope_head_dim"],
        qk_rope_head_dim=g["qk_rope_head_dim"], v_head_dim=g["v_head_dim"]))

    def both(fn):
        out, vjp = jax.vjp(fn, p, a)
        return (out,) + vjp(dy)

    with jax.default_matmul_precision("highest"):
        got = both(lambda p, a: model._attention(
            p, a, model._tables(0, seq)))
        want = both(lambda p, a: ref._attention(p, a, arch, None))
    return model, got, want


@pytest.fixture(scope="module")
def attention_pairs():
    cache = {}

    def get(geometry, flash):
        if (geometry, flash) not in cache:
            cache[geometry, flash] = _attention_pair(geometry, flash)
        return cache[geometry, flash]
    return get


def _rotary_columns(model, leaf, g):
    """The rotary columns of a weight gradient in the published order:
    `kv_a`'s last qk_rope columns (the sum over heads of the keys'
    gradient reaches them), `q_b`'s last qk_rope columns of every head
    (carried back through the halves order)."""
    c = model.c
    if leaf == "kv_a":
        return g[:, c.kv_lora_rank:]
    return g.reshape(-1, c.num_heads, c.qk_head_dim)[..., c.qk_nope_head_dim:]


@pytest.mark.parametrize("what", [
    "output", "input", *ATTN_LEAVES, "kv_a-rotary-columns",
    "q_b-rotary-columns"])
@pytest.mark.parametrize("geometry,flash", [
    ("toy", None), ("toy", True), ("cell4", None), ("cell4", True)],
    ids=["toy", "toy-kernels", "cell4", "cell4-kernels"])
def test_attention_matches_the_published_order_formulation(
        attention_pairs, geometry, flash, what):
    model, (out, dp, da), (w_out, w_dp, w_da) = attention_pairs(geometry,
                                                                flash)
    if what == "output":
        got, want = out, w_out
    elif what == "input":
        got, want = da, w_da
    elif what.endswith("-rotary-columns"):
        leaf = what.split("-")[0]
        got, want = (_rotary_columns(model, leaf, g[leaf])
                     for g in (dp, w_dp))
        assert got.shape[-1] == model.c.qk_rope_head_dim
    else:
        got, want = (g[what]["weight"] if what.endswith("norm") else g[what]
                     for g in (dp, w_dp))
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert scale > 0
    assert float(jnp.abs(got - want).max()) <= 1e-4 * scale, what


def test_weights_loaded_in_published_order_match_the_reference(mesh):
    """A checkpoint's weights, laid out as `config.json`'s family lays
    them out (a head's columns together, nope | rope, rotary pairs
    interleaved) and put into `params` as they are, at the cell's head
    geometry: the per-token losses are the reference's on the same
    arrays."""
    g = {k: v for k, v in GEOMETRY["cell4"].items()
         if k not in ("seq", "batch")}
    model = MLAMoE(MLAMoEConfig(
        vocab_size=64, hidden=32, q_lora_rank=24, kv_lora_rank=16,
        intermediate_size=48, moe_intermediate_size=8, n_routed_experts=16,
        num_experts_per_tok=3, first_k_dense_replace=1, num_expert_layers=1,
        experts_first=4, experts_count=8, rope_theta=1e4, init_std=0.3, **g))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    params = jax.tree.map(           # nothing of `init` but the shapes
        lambda sh: jnp.asarray(rng.normal(0, 0.3, sh.shape), jnp.float32),
        shapes)
    arch = dict(ARCH, num_attention_heads=32, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128, num_hidden_layers=2)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        got = on_mesh(model, mesh,
                      lambda p, t, l: model.token_losses(p, t, l)[:2],
                      (P(), P()))(params, tokens, labels)
    want = ref.token_losses(params, tokens, labels, arch=arch)
    for g_, w_ in zip(got, want):
        np.testing.assert_allclose(g_, w_, atol=5e-5, rtol=0)


# ------------------------------ what the staging promises ------------------------------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_attention_stages_q_and_k_in_one_pass_each():
    """Guards the mechanism at the level a jaxpr shows it, at the
    benchmark cell's shapes (2 x 4096, 32 heads, 128 + 64 / 128, bf16,
    the kernels forced): between the GEMMs and `flash_attention` no
    full-size q or k is transposed or concatenated (the head-major
    order is the staging pass's output layout, not a copy after it),
    k's rotary row is never broadcast to the heads, and the pass is one
    `rope_stage` call for q and one for k a block, `rope_unstage` the
    same in the backward.  v and the context, 128 wide, may be
    transposed once each way."""
    model = MLAMoE(MLAMoEConfig(dtype=jnp.bfloat16, flash_override=True))
    p = jax.eval_shape(lambda k: model._init_block(k, 1),
                       jax.random.PRNGKey(0))["attn"]
    a = jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16)

    def fwd_bwd(p, a):
        out, vjp = jax.vjp(lambda p, a: model._attention(
            p, a, model._tables(0, 4096)), p, a)
        return vjp(out)

    eqns = list(_eqns(jax.make_jaxpr(fwd_bwd)(p, a).jaxpr))
    calls = [e.params["name"] for e in eqns
             if e.primitive.name == "pallas_call"]
    assert calls.count("rope_stage") == 2
    assert calls.count("rope_unstage") == 2
    assert sum(c.startswith("flash_fwd") for c in calls) == 1
    big = 2 * 4096 * 32 * 64          # a rotary slice of every head
    for e in eqns:
        shapes = [tuple(v.aval.shape) for v in e.outvars]
        if e.primitive.name in ("transpose", "concatenate"):
            # only 128-wide v, context and their gradients; and weights,
            # which are smaller than any activation here
            assert all(s[-1] != 192 and (np.prod(s) < big or s[-1] == 128)
                       for s in shapes), (e.primitive.name, shapes)
        if e.primitive.name == "broadcast_in_dim":
            assert all(np.prod(s) < big for s in shapes), shapes
