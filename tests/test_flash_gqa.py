"""Grouped-query attention in the training flash kernels: fewer kv
heads than query heads in `flash_attention(q, k, v)`, against the dense
reference on k and v repeated a query head (interpret mode on the
CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import tune
from apex_tpu.ops import flash_attention as FA

B, HKV, S, D = 2, 2, 256, 32
NAMES = ("o", "dq", "dk", "dv")


def _qkvdo(group, dtype=jnp.float32, dv=D):
    ks = jax.random.split(jax.random.PRNGKey(group), 4)
    shapes = ((B, HKV * group, S, D), (B, HKV, S, D), (B, HKV, S, dv),
              (B, HKV * group, S, dv))
    return tuple(jax.random.normal(k, s, dtype) for k, s in zip(ks, shapes))


def _fwd_bwd(attn, q, k, v, do):
    def run(q, k, v, do):
        out, pull = jax.vjp(attn, q, k, v)
        return (out,) + pull(do)
    return jax.jit(run)(q, k, v, do)


def _repeated(group, **kw):
    return lambda q, k, v: FA.attention_reference(
        q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1), **kw)


@pytest.fixture(scope="module")
def pairs():
    """{(group, fused): (kernels, reference)}, each (o, dq, dk, dv),
    causal, two blocks an axis."""
    out = {}
    for group in (1, 2, 8):
        args = _qkvdo(group)
        want = _fwd_bwd(_repeated(group, causal=True), *args)
        for fused in (True, False):
            out[group, fused] = (_fwd_bwd(lambda q, k, v: FA.flash_attention(
                q, k, v, causal=True, use_pallas_override=True, block_q=128,
                block_k=128, fused_backward=fused), *args), want)
    return out


@pytest.mark.parametrize("what", range(4), ids=NAMES)
@pytest.mark.parametrize("fused", [True, False],
                         ids=["single_pass", "two_kernels"])
@pytest.mark.parametrize("group", [1, 2, 8])
def test_grouped_heads_match_the_reference_on_repeated_kv(pairs, group,
                                                          fused, what):
    got, want = pairs[group, fused]
    assert got[what].shape == want[what].shape
    np.testing.assert_allclose(got[what], want[what], atol=5e-5, rtol=5e-5)


def test_groups_with_two_widths_segments_and_no_mask():
    """The other operands of a call ride along: v of another width,
    segment ids, and a call without a causal mask."""
    q, k, v, do = _qkvdo(4, dv=16)
    seg = jnp.repeat(jnp.arange(4), S // 4)[None].repeat(B, 0)
    got = _fwd_bwd(lambda q, k, v: FA.flash_attention(
        q, k, v, segment_ids=seg, use_pallas_override=True, block_q=128,
        block_k=128), q, k, v, do)
    want = _fwd_bwd(_repeated(4, q_segment_ids=seg, kv_segment_ids=seg),
                    q, k, v, do)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


def test_no_repeated_copy_of_k_or_v_is_made():
    """The kernels are handed k and v with their own head count, and dk
    and dv come back with it from the single pass: no array of the
    query heads' count but q, o, do and dq."""
    q, k, v, do = _qkvdo(8, jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, v, do: jax.vjp(
        lambda q, k, v: FA.flash_attention(
            q, k, v, causal=True, use_pallas_override=True,
            fused_backward=True), q, k, v)[1](do))(q, k, v, do))
    assert "repeat" not in text
    bwd = text[text.index("name=flash_bwd"):]
    outs = bwd[bwd.index("out_avals"):bwd.index("]", bwd.index("out_avals")
                                                 + 200)]
    assert outs.count(f"bfloat16[{B * HKV},{S},{D}]") == 2     # dk, dv
    assert outs.count(f"bfloat16[{B * HKV * 8},{S},{D}]") == 1    # dq
    # and nothing of k's or v's is ever as large as q
    assert f"bf16[{B},{HKV * 8},{S},{D}] = broadcast" not in text


def test_the_fallback_repeats_for_the_dense_reference():
    q, k, v, do = _qkvdo(2)
    got = _fwd_bwd(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=True, use_pallas_override=False), q, k, v, do)
    want = _fwd_bwd(_repeated(2, causal=True), q, k, v, do)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("heads,kv_heads,v_heads", [(6, 4, 4), (8, 2, 4)])
def test_heads_that_do_not_group_evenly_are_refused(heads, kv_heads, v_heads):
    q = jnp.zeros((1, heads, 128, 32))
    k = jnp.zeros((1, kv_heads, 128, 32))
    v = jnp.zeros((1, v_heads, 128, 32))
    with pytest.raises(ValueError, match="equal groups"):
        FA.flash_attention(q, k, v, causal=True)


def test_the_tuner_key_has_the_kv_heads_only_where_they_are_fewer():
    one = tune.flash_attrs(1, 64, 4096, 4096, 128, jnp.bfloat16, True)
    same = tune.flash_attrs(1, 64, 4096, 4096, 128, jnp.bfloat16, True,
                            hkv=64)
    grouped = tune.flash_attrs(1, 64, 4096, 4096, 128, jnp.bfloat16, True,
                               hkv=8)
    assert one == same and "hkv" not in one
    assert grouped == dict(one, hkv=8)


@pytest.mark.parametrize("group", [1, 4])
def test_a_tuned_config_is_looked_up_under_the_kv_heads(monkeypatch, group):
    seen = []
    monkeypatch.setattr(tune, "tuned",
                        lambda op, attrs: seen.append((op, attrs)))
    q, k, v, _ = _qkvdo(group, jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=True, use_pallas_override=True), q, k, v)
    assert seen == [("flash_sdpa", tune.flash_attrs(
        B, HKV * group, S, S, D, jnp.bfloat16, True, hkv=HKV))]
    assert ("hkv" in seen[0][1]) == (group > 1)


def test_the_committed_v5e_config_for_the_grouped_call():
    from apex_tpu.tune import defaults

    key = tune.make_key("flash_sdpa", tune.flash_attrs(
        1, 64, 4096, 4096, 128, "bfloat16", True, hkv=8))
    config = defaults.DEFAULTS["v5e"][key]["config"]
    assert FA._checked_tuned_config(config, 4096, 4096, 128) == config
    # the single pass: dk and dv are summed over a group inside its grid
    assert config["fused_bwd"] is True
