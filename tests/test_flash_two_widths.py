"""Flash attention whose keys and values differ in width (latent
attention: q and k 192 wide, v 128): forward and backward of every
kernel path against `attention_reference` in interpret mode, and that
with one width the kernels trace to the program they traced to before
the second width could be told apart."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import tune
from apex_tpu.ops import flash_attention as FA

D_QK, D_V = 192, 128


def _qkvdo(b, h, s, dtype, d_qk=D_QK, d_v=D_V):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(ks[0], (b, h, s, d_qk), dtype),
            jax.random.normal(ks[1], (b, h, s, d_qk), dtype),
            jax.random.normal(ks[2], (b, h, s, d_v), dtype),
            jax.random.normal(ks[3], (b, h, s, d_v), dtype))


def _fwd_bwd(attn, q, k, v, do):
    out, vjp = jax.vjp(attn, q, k, v)
    return (out,) + vjp(do)


# the fused single-pass backward and the two-kernel backward (the path
# 4096 x 192 takes by the cap: a whole head's dk and dv sums pass it)
@pytest.mark.parametrize("path,cap", [("fused", None), ("two_kernel", 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_two_widths_match_the_reference(monkeypatch, path, cap, causal):
    if cap is not None:
        monkeypatch.setattr(FA, "_FUSED_BWD_CAP", cap)
    q, k, v, do = _qkvdo(1, 2, 256, jnp.float32)
    got = _fwd_bwd(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=causal, use_pallas_override=True, block_q=128,
        block_k=128), q, k, v, do)
    want = _fwd_bwd(lambda q, k, v: FA.attention_reference(
        q, k, v, causal=causal), q, k, v, do)
    # o and dv have v's width, dq and dk the keys'
    assert [a.shape[-1] for a in got] == [D_V, D_QK, D_QK, D_V]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5)


def test_two_widths_in_bf16_and_the_default_scale():
    """The default softmax scale is 1/sqrt(the keys' width)."""
    q, k, v, do = _qkvdo(2, 2, 128, jnp.bfloat16)
    got = _fwd_bwd(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=True, use_pallas_override=True), q, k, v, do)
    want = _fwd_bwd(lambda q, k, v: FA.attention_reference(
        q, k, v, causal=True, softmax_scale=D_QK ** -0.5), q, k, v, do)
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_no_operand_is_padded_to_anothers_width():
    """Every array of the traced program that has a head's width has
    its own: at a sequence of 384 in blocks of 128 no dimension is 256
    (the keys' width padded to whole lanes, or v's to the keys'), and
    the kernels' operands and results are 192 and 128 wide."""
    q, k, v, do = _qkvdo(1, 2, 384, jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda *a: _fwd_bwd(
        lambda q, k, v: FA.flash_attention(
            q, k, v, causal=True, use_pallas_override=True), *a))(
        q, k, v, do)).replace(" ", "")
    assert "256]" not in text and "[256," not in text
    # flattened to (b*h, s, d): q, k, dq, dk and v, o, do, dv
    assert text.count("bf16[2,384,192]") >= 4
    assert text.count("bf16[2,384,128]") >= 4


# sha256 of str(jax.make_jaxpr(...)), forward and backward in one jaxpr,
# taken with this installation's JAX.  The first four were taken at
# cd387b4, the parent of the PR that cut causal blocks into compute
# tiles inside `_fwd_kernel` and `_bwd_fused_kernel`: that PR changes
# what a causal call through those two bodies traces to, on purpose, so
# what is pinned is what it must not touch.  Without a mask the three
# paths through them trace to the program they had (the bypass), and so
# does the causal two-kernel backward, traced alone because its forward
# is `_fwd_kernel`'s.
PARENT_JAXPR = {
    "head_major_d64": "012f2236b9b727aea33ea42187a0fe3b872c4a2334a02b48a03533"
                      "435be9a40a",
    "head_major_d128": "7cb76e2b4ab1e00e2d3ffb608f4b1cd4e040e5956761db8dfad619"
                       "557143133d",
    "two_kernel_s8192": "f1cac71d58a9005ca8b36b3444f0f977971141217ce9d6103b2b9"
                        "318dc8e31ab",
    "projection_layout": "3136c875f3a2c3ddc23dcad97d4d756ca01b0ecbdac9f411225f"
                         "47f8c6b5e353",
    # the flash call of each benchmark cell, causal, bf16, taken at
    # bec3780, the parent of the PR that deleted the head-packed kernels
    # and moved a call's kernel shape into one function: every cell
    # traces to the program it traced to
    "cell_gpt2_medium_b12s1024": "1bf746fc42a63adbcdb9d538b02cec32278e7f2d2d43"
                                 "c465cc8a6736412401ad",
    "cell_gpt_1p3b_b7s512": "20435f492a50074803701a879ac7450bb28d1cabe93d13a6b"
                            "6db8b721bf7007d",
    "cell_gpt_1p3b_tp2dp2_b8s1024": "2a071ad78b67813b8f12a7ac3308b81b061511f2b"
                                    "d5c35d18a79cfc02acf00be",
    "cell_joyai_b2s4096_192_128": "6bf1eab9a8716d7ddb9c2aa6ceade2129e020b995ee"
                                  "bb8c6a03424881a13c807",
    # the grouped-query call of the fifth cell, taken at the PR that
    # taught the kernels groups (PR 32): eight query heads a kv head
    "cell_solar_open2_b1s4096_64_on_8": "df29d241d5b863a1535b50d0e5c6203424dc9"
                                        "08ad816c09ec7e7620fd8564998",
}


def _head_major_text(shape, causal, **kw):
    q = jnp.zeros(shape, jnp.bfloat16)
    return str(jax.make_jaxpr(lambda q, k, v: jax.vjp(
        lambda q, k, v: FA.flash_attention(
            q, k, v, causal=causal, use_pallas_override=True, **kw),
        q, k, v)[1](q))(q, q, q))


def _two_kernel_backward_text():
    """The causal backward of a sequence past the fused kernel's cap,
    `flash_bwd_dq` and `flash_bwd_dkv`, without its forward."""
    q = jnp.zeros((1, 2, 8192, 64), jnp.bfloat16)
    lse = jnp.zeros((1, 2, 8192), jnp.float32)
    shape = FA._kernel_shape(8192, 8192, 64, 64, q.dtype, True)
    assert not shape.fused_bwd
    text = str(jax.make_jaxpr(lambda q, k, v, o, lse, do: FA._bwd_impl(
        q, k, v, o, lse, do, 0.125, True, shape)[:3])(q, q, q, q, lse, q))
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text
    return text


def _projection_text(s=256, b=2, nh=4, causal=False):
    x = jax.ShapeDtypeStruct((s, b, 3 * nh * 64), jnp.bfloat16)
    return str(jax.make_jaxpr(lambda x: jax.vjp(
        lambda x: FA.flash_attention_qkv(
            x, nh, causal=causal, use_pallas_override=True),
        x)[1](jnp.zeros((s, b, nh * 64), jnp.bfloat16)))(x))


def _latent_text():
    """Cell 4's call: keys 192 and values 128 wide, head-major, under
    the committed v5e config, handed over as `test_chip_compile.py`
    hands it (the tuner sees a CPU here)."""
    from apex_tpu.tune import defaults
    key = tune.make_key("flash_sdpa", tune.flash_attrs(
        2, 32, 4096, 4096, D_QK, "bfloat16", True, dv=D_V))
    config = dict(defaults.DEFAULTS["v5e"][key]["config"])
    config["fused_backward"] = config.pop("fused_bwd")
    q = jax.ShapeDtypeStruct((2, 32, 4096, D_QK), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 32, 4096, D_V), jnp.bfloat16)
    return str(jax.make_jaxpr(lambda q, k, v: jax.vjp(
        lambda q, k, v: FA.flash_attention(
            q, k, v, causal=True, use_pallas_override=True, **config),
        q, k, v)[1](jnp.zeros(v.shape, v.dtype)))(q, q, v))


def _grouped_text():
    """Cell 5's call: 64 query heads on 8 kv heads of 128, 1 x 4096,
    head-major, under the committed v5e config."""
    from apex_tpu.tune import defaults
    key = tune.make_key("flash_sdpa", tune.flash_attrs(
        1, 64, 4096, 4096, 128, "bfloat16", True, hkv=8))
    config = dict(defaults.DEFAULTS["v5e"][key]["config"])
    config["fused_backward"] = config.pop("fused_bwd")
    q = jax.ShapeDtypeStruct((1, 64, 4096, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8, 4096, 128), jnp.bfloat16)
    return str(jax.make_jaxpr(lambda q, k, v: jax.vjp(
        lambda q, k, v: FA.flash_attention(
            q, k, v, causal=True, use_pallas_override=True, **config),
        q, k, v)[1](jnp.zeros(q.shape, q.dtype)))(q, k, k))


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the recorded texts are JAX 0.9.0's")
@pytest.mark.parametrize("case,trace", [
    ("head_major_d64", lambda: _head_major_text((2, 4, 256, 64), False)),
    ("head_major_d128", lambda: _head_major_text((1, 2, 512, 128), False)),
    ("two_kernel_s8192", _two_kernel_backward_text),
    ("projection_layout", _projection_text),
    ("cell_gpt2_medium_b12s1024", lambda: _projection_text(1024, 12, 16, True)),
    ("cell_gpt_1p3b_b7s512", lambda: _projection_text(512, 7, 32, True)),
    ("cell_gpt_1p3b_tp2dp2_b8s1024",
     lambda: _projection_text(1024, 8, 16, True)),
    ("cell_joyai_b2s4096_192_128", _latent_text),
    ("cell_solar_open2_b1s4096_64_on_8", _grouped_text),
])
def test_one_width_traces_to_the_parents_jaxpr(case, trace):
    assert hashlib.sha256(trace().encode()).hexdigest() == PARENT_JAXPR[case]


def test_the_tuner_key_has_the_second_width_only_where_it_differs():
    one = tune.flash_attrs(2, 32, 4096, 4096, 128, jnp.bfloat16, True)
    same = tune.flash_attrs(2, 32, 4096, 4096, 128, jnp.bfloat16, True,
                            dv=128)
    two = tune.flash_attrs(2, 32, 4096, 4096, 192, jnp.bfloat16, True, dv=128)
    assert one == same and "dv" not in one
    assert two["d"] == 192 and two["dv"] == 128
    assert tune.make_key("flash_sdpa", two) != tune.make_key(
        "flash_sdpa", dict(two, dv=192))


def test_a_tuned_config_is_looked_up_under_both_widths(monkeypatch):
    seen = []
    monkeypatch.setattr(tune, "tuned",
                        lambda op, attrs: seen.append((op, attrs)))
    q, k, v, _ = _qkvdo(1, 2, 128, jnp.bfloat16)
    FA.flash_attention(q, k, v, causal=True, use_pallas_override=True)
    assert seen == [("flash_sdpa", tune.flash_attrs(
        1, 2, 128, 128, D_QK, jnp.bfloat16, True, dv=D_V))]


@pytest.mark.parametrize("fused", [True, False, None])
def test_a_tuned_config_decides_the_backward(monkeypatch, fused):
    """`fused_bwd` of a tuned config (or `fused_backward=`) overrules
    the VMEM cap's choice between the single-pass backward and the two
    kernels, either way, and the gradients are the same."""
    # a cap under which the heuristic takes the two kernels
    monkeypatch.setattr(FA, "_FUSED_BWD_CAP", 1)
    config = {"block_q": 128, "block_k": 128}
    if fused is not None:
        config["fused_bwd"] = fused
    monkeypatch.setattr(tune, "tuned", lambda op, attrs: config)
    q, k, v, do = _qkvdo(1, 2, 256, jnp.float32)

    def run(q, k, v, do):
        return _fwd_bwd(lambda q, k, v: FA.flash_attention(
            q, k, v, causal=True, use_pallas_override=True), q, k, v, do)

    text = str(jax.make_jaxpr(run)(q, k, v, do))
    assert ("name=flash_bwd\n" in text) == bool(fused)
    assert ("name=flash_bwd_dkv" in text) == (not fused)
    want = _fwd_bwd(lambda q, k, v: FA.attention_reference(
        q, k, v, causal=True), q, k, v, do)
    for g, w in zip(run(q, k, v, do), want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5)


def test_the_committed_v5e_config_for_latent_attention(monkeypatch):
    from apex_tpu.tune import defaults

    key = tune.make_key("flash_sdpa", tune.flash_attrs(
        2, 32, 4096, 4096, D_QK, "bfloat16", True, dv=D_V))
    config = defaults.DEFAULTS["v5e"][key]["config"]
    assert config == {"block_q": 1024, "block_k": 512, "fused_bwd": True}
    # FA's validation of a cache hit lets it through, and not a
    # `fused_bwd` that is no bool: then the heuristics' blocks and the
    # two kernels the cap sends 4096 x 192 / 128 to
    monkeypatch.setattr(tune, "tuned", lambda op, attrs: config)
    args = (4096, 4096, D_QK, D_V, jnp.bfloat16, True)
    assert FA._kernel_shape(*args, tuner_key=(2, 32, False)) == (
        1024, 512, 512, 256, True)
    monkeypatch.setattr(tune, "tuned",
                        lambda op, attrs: dict(config, fused_bwd="yes"))
    with pytest.warns(UserWarning, match="out-of-range tuned config"):
        assert FA._kernel_shape(*args, tuner_key=(2, 32, False)) == (
            512, 1024, 512, 512, False)
