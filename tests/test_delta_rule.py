"""`ops.delta_rule.gated_delta_rule`: the chunked form against the
recurrence a token at a time, forward and all five gradients, at tiny
sizes on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import tune
from apex_tpu.ops import delta_rule as DR

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def _inputs(seed, b=2, n=3, s=128, dk=16, dv=8, neg=True, decay=2.0,
            dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(key, scale=1.0):
        x = jax.random.normal(key, (b, n, s, dk))
        return (x * scale / jnp.linalg.norm(x, axis=-1, keepdims=True)
                ).astype(dtype)
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, n, s, dk)) - 2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, n, s)))
    return ((unit(ks[0], dk ** -0.5), unit(ks[1]),
             jax.random.normal(ks[2], (b, n, s, dv), dtype), g,
             2 * beta if neg else beta),
            jax.random.normal(ks[5], (b, n, s, dv)))


def _fwd_bwd(rule, args, do):
    def run(args, do):
        out, pull = jax.vjp(rule, *args)
        return (out,) + pull(do.astype(out.dtype))
    return jax.jit(run)(args, do)


@pytest.fixture(scope="module")
def pairs():
    """{(chunk, neg): (chunked, recurrence)}, each (o, dq, dk, dv, dg,
    dbeta), batch 2, 3 heads, d_k 16, d_v 8."""
    out = {}
    for chunk in (16, 32, 64):
        for neg in (True, False):
            args, do = _inputs(chunk + neg, neg=neg)
            out[chunk, neg] = (
                _fwd_bwd(lambda *a: DR.gated_delta_rule(*a, chunk=chunk),
                         args, do),
                _fwd_bwd(DR.gated_delta_rule_reference, args, do))
    return out


@pytest.mark.parametrize("what", range(6), ids=NAMES)
@pytest.mark.parametrize("neg", [True, False], ids=["beta<2", "beta<1"])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_chunked_form_matches_the_token_recurrence(pairs, chunk, neg, what):
    got, want = pairs[chunk, neg]
    scale = float(jnp.max(jnp.abs(want[what])))
    np.testing.assert_allclose(got[what], want[what], rtol=1e-4,
                               atol=2e-5 * max(scale, 1.0))


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_channel_that_forgets_everything_overflows_nowhere(chunk):
    """Log-decays down to -60 a token: exp(-G) of a chunk would be
    inf; nothing here divides by a decay."""
    args, do = _inputs(5, decay=40.0)
    assert float(args[3].min()) < -40
    got = _fwd_bwd(lambda *a: DR.gated_delta_rule(*a, chunk=chunk), args, do)
    want = _fwd_bwd(DR.gated_delta_rule_reference, args, do)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=5e-5)


@pytest.mark.parametrize("heads", [1, 3])
def test_heads_a_few_at_a_time_are_the_same_numbers(pairs, heads):
    args, do = _inputs(32 + True)
    got = _fwd_bwd(lambda *a: DR.gated_delta_rule(
        *a, chunk=32, heads_a_pass=heads), args, do)
    for g, w in zip(got, pairs[32, True][0]):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


def test_heads_a_pass_must_divide_the_heads():
    args, _ = _inputs(4)
    with pytest.raises(ValueError, match="does not divide"):
        DR.gated_delta_rule(*args, chunk=32, heads_a_pass=2)


def test_bf16_operands_stay_within_bf16_of_the_recurrence():
    args, do = _inputs(7, dtype=jnp.bfloat16)
    got = _fwd_bwd(lambda *a: DR.gated_delta_rule(*a, chunk=32), args, do)
    want = _fwd_bwd(DR.gated_delta_rule_reference, args, do)
    assert got[0].dtype == jnp.bfloat16 and got[4].dtype == jnp.float32
    for g, w in zip(got, want):
        rms = float(jnp.sqrt(jnp.mean(jnp.square(w.astype(jnp.float32)))))
        err = jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32))
        assert float(jnp.max(err)) < 0.15 * rms


def test_the_forward_keeps_a_state_a_chunk_not_a_token():
    args, _ = _inputs(1)
    _, res = jax.eval_shape(lambda *a: DR._delta_rule_fwd(*a, 32), *args)
    assert [r.shape for r in res[:5]] == [a.shape for a in args]
    assert res[5].shape == (2, 3, 128 // 32, 16, 8)
    assert res[5].dtype == jnp.float32 and len(res) == 6


def test_stats_count_calls_chunk_and_saved_states():
    DR.reset_stats()
    args, do = _inputs(2)
    # counted while tracing: a differentiated call once
    jax.eval_shape(lambda a, do: jax.vjp(
        lambda *x: DR.gated_delta_rule(*x, chunk=16), *a)[1](do), args, do)
    jax.eval_shape(lambda *a: DR.gated_delta_rule(*a, chunk=64), *args)
    assert DR.stats() == {
        "calls": 2, "chunk": 64,
        "saved_state_bytes": 4 * 2 * 3 * 16 * 8 * (128 // 16 + 128 // 64)}
    DR.reset_stats()
    assert DR.stats() == {"calls": 0, "chunk": 0, "saved_state_bytes": 0}


def test_the_chunk_comes_from_the_tuner_then_the_default(monkeypatch):
    args, _ = _inputs(3)
    seen = []

    def tuned(op, attrs):
        seen.append((op, attrs))
        return {"chunk": 32}
    monkeypatch.setattr(tune, "tuned", tuned)
    DR.reset_stats()
    jax.eval_shape(lambda *a: DR.gated_delta_rule(*a), *args)
    assert seen == [("delta_rule", tune.delta_rule_attrs(
        2, 3, 128, 16, 8, jnp.float32))]
    assert DR.stats()["chunk"] == 32
    monkeypatch.setattr(tune, "tuned", lambda op, attrs: None)
    jax.eval_shape(lambda *a: DR.gated_delta_rule(*a), *args)
    assert DR.stats()["chunk"] == DR.DEFAULT_CHUNK
    # a sequence the default does not divide takes a smaller power of two
    short = tuple(a[:, :, :48] for a in args)
    jax.eval_shape(lambda *a: DR.gated_delta_rule(*a), *short)
    assert DR.stats()["chunk"] == 16


def test_the_committed_v5e_chunk_is_the_benchmark_shapes():
    from apex_tpu.tune import defaults

    key = tune.make_key("delta_rule", tune.delta_rule_attrs(
        1, 64, 4096, 128, 128, "bfloat16"))
    config = defaults.DEFAULTS["v5e"][key]["config"]
    assert set(config) == {"chunk", "heads"}
    assert 4096 % config["chunk"] == 0 and 64 % config["heads"] == 0


@pytest.mark.parametrize("chunk", [0, 24, 256])
def test_a_chunk_that_is_no_dividing_power_of_two_is_refused(chunk):
    args, _ = _inputs(4)
    with pytest.raises(ValueError, match="power of two"):
        DR.gated_delta_rule(*args, chunk=chunk)


def test_mismatched_shapes_are_refused():
    (q, k, v, g, beta), _ = _inputs(4)
    with pytest.raises(ValueError, match="shapes"):
        DR.gated_delta_rule(q, k, v, g[:, :, :, :8], beta)
    with pytest.raises(ValueError, match="shapes"):
        DR.gated_delta_rule(q, k, v, g, beta[:, :1])


@pytest.mark.parametrize("m", [1, 2, 8, 16, 32, 64])
def test_the_triangular_inverse_by_halves(m):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(m), (3, m, m)), -1)
    got = DR._tri_inv(a)
    want = np.linalg.inv(np.eye(m) + np.asarray(a, np.float64))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * np.abs(
        want).max())


def test_no_token_by_token_loop_in_the_chunked_program():
    """The only loops are over the chunks: S / chunk steps forward and
    as many back, whatever the sequence."""
    args, do = _inputs(6, s=256)
    text = str(jax.make_jaxpr(lambda a, do: _fwd_bwd(
        lambda *x: DR.gated_delta_rule(*x, chunk=64), a, do))(args, do))
    lengths = [int(part.split()[0].rstrip(",")) for part in
               text.split("length=")[1:]]
    assert lengths and set(lengths) == {256 // 64}
