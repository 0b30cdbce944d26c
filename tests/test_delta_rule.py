"""`ops.delta_rule.gated_delta_rule`: the chunked form against the
recurrence a token at a time, forward and all five gradients, at tiny
sizes on the CPU."""

import functools

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
    assert res[5].shape == (128 // 32, 2, 3, 16, 8)     # the chunk first
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
        "saved_state_bytes": 4 * 2 * 3 * 16 * 8 * (128 // 16 + 128 // 64),
        "kernel_calls": 0, "scalar_calls": 0, "scalar_kernel_calls": 0,
        "state_elems": 2 * 16 * 8, "state_lane_elems": 2 * 16 * 128}
    assert list(DR.stats())[:2] == ["calls", "chunk"]
    DR.reset_stats()
    assert set(DR.stats().values()) == {0}


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


@pytest.mark.parametrize("heads,seq", [(64, 4096), (32, 8192)])
def test_the_committed_v5e_chunk_is_the_benchmark_shapes(heads, seq):
    from apex_tpu.tune import defaults

    key = tune.make_key("delta_rule", tune.delta_rule_attrs(
        1, heads, seq, 128, 128, "bfloat16"))
    config = defaults.DEFAULTS["v5e"][key]["config"]
    # re-measured on the kernels (PRs 33, 34): every head in one call
    assert set(config) == {"chunk"}
    assert seq % config["chunk"] == 0
    assert DR._kernels_take(
        jax.ShapeDtypeStruct((1, heads, seq, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, heads, seq, 128), jnp.bfloat16),
        config["chunk"], True)


def test_the_committed_v5e_config_of_a_heads_decay_at_96_and_192():
    """The Olmo-Hybrid cell's scan finds its measured entry: chunk 128,
    ten heads a call, on the Pallas pair at its widths."""
    from apex_tpu.tune import defaults

    key = tune.make_key("delta_rule", tune.delta_rule_attrs(
        1, 30, 8192, 96, 192, "bfloat16"))
    config = defaults.DEFAULTS["v5e"][key]["config"]
    assert config == {"chunk": 128, "heads": 10} and 30 % config["heads"] == 0
    assert DR._kernels_take(
        jax.ShapeDtypeStruct((1, 30, 8192, 96), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, 30, 8192, 192), jnp.bfloat16),
        config["chunk"], True)


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


# ------------------- the chunk-local stage as Pallas kernels -------------------
# interpret mode here; tests/test_chip_compile.py compiles the pair for
# the chip and chip_smoke.py::delta_rule_grads runs it there

LOCALS = DR._Locals._fields
GRADS = ("dq", "dk", "dv", "dg", "dbeta")
WIDE = dict(b=1, n=2, s=128, dk=128, dv=128)


def _close(got, want, rel):
    """Within `rel` of the largest entry: bf16 outputs may land on the
    neighbouring bf16."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rel * max(np.abs(want).max(), 1e-30)


@pytest.fixture(scope="module")
def stages():
    """{(chunk, dtype name): (kernels, jax.numpy)}, each (the six of
    `_Locals`, the five gradients under one seeded cotangent)."""
    out = {}
    for chunk in (32, 64):
        for dtype in (jnp.float32, jnp.bfloat16):
            args, _ = _inputs(chunk, dtype=dtype, **WIDE)

            def run(stage, args):
                loc, pull = jax.vjp(lambda *a: stage(*a, chunk), *args)
                keys = jax.random.split(jax.random.PRNGKey(9), len(loc))
                cot = DR._Locals(*(
                    jax.random.normal(k, x.shape).astype(x.dtype)
                    for k, x in zip(keys, loc)))
                return tuple(loc), pull(cot)
            out[chunk, dtype.__name__] = (
                jax.jit(functools.partial(run, DR._locals_kernels))(args),
                jax.jit(functools.partial(run, DR._locals))(args))
    return out


@pytest.mark.parametrize("what", range(6), ids=LOCALS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [32, 64])
def test_kda_locals_fwd_matches_the_compiled_stage(stages, chunk, dtype, what):
    got, want = (x[0][what] for x in stages[chunk, dtype])
    assert got.dtype == want.dtype
    _close(got, want, 2e-5 if dtype == "float32" else 8e-3)


@pytest.mark.parametrize("what", range(5), ids=GRADS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [32, 64])
def test_kda_locals_bwd_matches_the_compiled_stages_pullback(stages, chunk,
                                                             dtype, what):
    got, want = (x[1][what] for x in stages[chunk, dtype])
    assert got.dtype == want.dtype
    _close(got, want, 2e-5 if dtype == "float32" else 8e-3)


def _forced(chunk):
    return lambda *a: DR.gated_delta_rule(*a, chunk=chunk,
                                          use_pallas_override=True)


@pytest.fixture(scope="module")
def forced_pairs():
    """{neg: (the op with the kernels forced, the recurrence)} at 128-
    wide heads, chunk 64."""
    out = {}
    for neg in (True, False):
        args, do = _inputs(70 + neg, neg=neg, **WIDE)
        out[neg] = (_fwd_bwd(_forced(64), args, do),
                    _fwd_bwd(DR.gated_delta_rule_reference, args, do))
    return out


@pytest.mark.parametrize("what", range(6), ids=NAMES)
@pytest.mark.parametrize("neg", [True, False], ids=["beta<2", "beta<1"])
def test_the_op_through_the_kernels_matches_the_token_recurrence(
        forced_pairs, neg, what):
    got, want = forced_pairs[neg]
    scale = float(jnp.max(jnp.abs(want[what])))
    np.testing.assert_allclose(got[what], want[what], rtol=1e-4,
                               atol=2e-5 * max(scale, 1.0))


@pytest.mark.parametrize("chunk", [32, 64])
def test_a_channel_that_forgets_everything_through_the_kernels(chunk):
    args, do = _inputs(5, decay=40.0, **WIDE)
    assert float(args[3].min()) < -40
    got = _fwd_bwd(_forced(chunk), args, do)
    want = _fwd_bwd(DR.gated_delta_rule_reference, args, do)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=5e-5)


@pytest.mark.parametrize("shape,chunk,kernels", [
    (dict(dk=128, dv=128), 64, 1), (dict(dk=128, dv=128), 32, 1),
    (dict(dk=16, dv=8), 64, 0), (dict(dk=8, dv=8), 32, 0),
    (dict(dk=128, dv=64), 64, 0), (dict(dk=128, dv=128), 16, 0)],
    ids=["128", "128-chunk32", "16x8", "8x8", "128x64", "128-chunk16"])
def test_only_whole_lane_tiles_and_a_written_chunk_take_the_kernels(
        shape, chunk, kernels):
    """Whatever the override says, a head that leaves more than a
    quarter of its lane tiles empty (8, 16, 64 wide) or a chunk the
    kernels are not written for takes the compiled stage and counts no
    kernel call.  96 and 192 are the widths below a whole tile that
    go (`test_the_committed_v5e_config_of_a_heads_decay_at_96_and_192`,
    `test_the_counters_of_a_heads_decay`)."""
    args, _ = _inputs(2, b=1, n=2, s=128, **shape)
    DR.reset_stats()
    text = str(jax.make_jaxpr(_forced(chunk))(*args))
    assert DR.stats()["calls"] == 1
    assert DR.stats()["kernel_calls"] == kernels
    assert ("kda_locals_fwd" in text) == bool(kernels)
    DR.reset_stats()


def test_off_the_chip_a_call_takes_the_compiled_stage():
    args, _ = _inputs(2, **WIDE)
    DR.reset_stats()
    text = str(jax.make_jaxpr(
        lambda *a: DR.gated_delta_rule(*a, chunk=64))(*args))
    assert "pallas_call" not in text and DR.stats()["kernel_calls"] == 0
    DR.reset_stats()


@pytest.mark.parametrize("heads,passes", [(None, 1), (1, 2)])
def test_a_differentiated_call_runs_the_stage_once_forward_twice_back(
        heads, passes):
    """By name: the forward holds one `kda_locals_fwd` a pass of heads;
    the backward recomputes the stage with one more and pulls back
    through one `kda_locals_bwd`."""
    args, do = _inputs(3, **WIDE)
    rule = lambda *a: DR.gated_delta_rule(
        *a, chunk=64, heads_a_pass=heads, use_pallas_override=True)

    def names(fn, *a):
        text = str(jax.make_jaxpr(fn)(*a))
        return (text.count("name=kda_locals_fwd"),
                text.count("name=kda_locals_bwd"))

    assert names(rule, *args) == (passes, 0)
    _, pull = jax.vjp(rule, *args)
    assert names(pull, do) == (passes, passes)
    assert names(lambda a, do: _fwd_bwd(rule, a, do), args, do) == (
        2 * passes, passes)


# --------------------- documents packed into one row ---------------------
# `resets` marks the tokens that start a document: the packed row has to
# give, document by document, what each document gives in a row of its
# own (the recurrence a token at a time, over that document's slice)

# a boundary at every position class of a chunk of 32 and of 64: on the
# chunk's edge (64, 128), one past it (129), one before it (191), on and
# beside a sub-block's edge (16, 17), in a sub-block (40), documents of
# 1 and 2 tokens (40-41, 41-43), and one that outlasts two chunks
STARTS = (0, 16, 17, 40, 41, 43, 64, 128, 129, 191, 250, 255)
PACKED = 256


def _resets(b, s=PACKED, starts=STARTS):
    first = np.zeros((b, s), bool)
    first[:, list(starts)] = True
    return jnp.asarray(first)


def _document_by_document(args, do, starts=STARTS):
    """(o, dq, dk, dv, dg, dbeta) of the recurrence over each document
    alone, laid side by side along the row."""
    s = do.shape[2]
    edges = list(starts) + [s]
    parts = []
    for a, z in zip(edges[:-1], edges[1:]):
        cut = lambda x: x[:, :, a:z]
        parts.append(_fwd_bwd(DR.gated_delta_rule_reference,
                              tuple(cut(x) for x in args), cut(do)))
    return tuple(jnp.concatenate(x, axis=2) for x in zip(*parts))


@pytest.fixture(scope="module")
def packed():
    """{(path, chunk): (the packed row through the op, document by
    document)}; the `jax.numpy` stage at 16 x 8 heads, the kernels
    interpreted at 128 x 128."""
    out = {}
    for path, shape, override in (
            ("jnp", dict(b=2, n=3, s=PACKED, dk=16, dv=8), None),
            ("kernels", dict(b=1, n=2, s=PACKED, dk=128, dv=128), True)):
        for chunk in (32, 64):
            args, do = _inputs(chunk + 11, neg=False, **shape)
            rule = lambda *a: DR.gated_delta_rule(
                *a, resets=_resets(shape["b"]), chunk=chunk,
                use_pallas_override=override)
            out[path, chunk] = (_fwd_bwd(rule, args, do),
                                _document_by_document(args, do))
    return out


@pytest.mark.parametrize("what", range(6), ids=NAMES)
@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("path", ["jnp", "kernels"])
def test_a_packed_row_is_its_documents_one_by_one(packed, path, chunk, what):
    got, want = packed[path, chunk]
    scale = float(jnp.max(jnp.abs(want[what])))
    np.testing.assert_allclose(got[what], want[what], rtol=1e-4,
                               atol=2e-5 * max(scale, 1.0))


@pytest.mark.parametrize("path", ["jnp", "kernels"])
def test_the_decay_of_a_first_token_gets_no_gradient(packed, path):
    dg = np.asarray(packed[path, 64][0][4])
    assert not dg[:, :, list(STARTS)].any()
    assert dg[:, :, 1].any()


def test_the_recurrence_with_exact_resets_is_document_by_document():
    args, do = _inputs(3, b=1, n=2, s=PACKED, neg=False)
    got = _fwd_bwd(lambda *a: DR.gated_delta_rule_reference(
        *a, resets=_resets(1)), args, do)
    for g, w in zip(got, _document_by_document(args, do)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", ["jnp", "kernels"])
def test_a_state_that_lives_a_thousand_tokens_is_gone_past_a_boundary(path):
    """g = -1e-3 on every channel: without the boundary the second
    document would read a state that has kept nine tenths of the
    first; with it, what each document reads alone."""
    shape, override = ((dict(b=1, n=2, s=128, dk=16, dv=8), None)
                       if path == "jnp" else (WIDE, True))
    args, do = _inputs(13, neg=False, **shape)
    args = args[:3] + (jnp.full_like(args[3], -1e-3), args[4])
    starts = (0, 70)
    rule = lambda *a: DR.gated_delta_rule(
        *a, resets=_resets(shape["b"], 128, starts), chunk=64,
        use_pallas_override=override)
    got = _fwd_bwd(rule, args, do)
    want = _document_by_document(args, do, starts)
    unbounded = DR.gated_delta_rule_reference(*args)
    assert float(jnp.max(jnp.abs(unbounded - want[0])[:, :, 71])) > 1e-2
    for g, w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=2e-5 * max(scale, 1.0))


def test_without_resets_the_op_traces_as_it_did():
    """None is no argument at all, and a mask is one `select` on g in
    front of the same program: the op has no second path for packed
    rows."""
    args, _ = _inputs(2, b=1, n=2, s=128)
    resets = _resets(1, 128, (0, 50))
    plain = lambda *a: DR.gated_delta_rule(*a, chunk=32)
    assert str(jax.make_jaxpr(plain)(*args)) == str(jax.make_jaxpr(
        lambda *a: DR.gated_delta_rule(*a, resets=None, chunk=32))(*args))
    pinned = lambda q, k, v, g, beta: plain(q, k, v, jnp.where(
        resets[:, None, :, None], jnp.float32(DR.RESET_LOG_DECAY), g), beta)
    assert str(jax.make_jaxpr(pinned)(*args)) == str(jax.make_jaxpr(
        lambda *a: DR.gated_delta_rule(*a, resets=resets, chunk=32))(*args))


def test_resets_of_another_shape_are_refused():
    args, _ = _inputs(4, s=64)
    with pytest.raises(ValueError, match="resets"):
        DR.gated_delta_rule(*args, resets=jnp.zeros((2, 32), bool), chunk=32)


# -------------------- one decay a head (Gated DeltaNet) --------------------
# g of (B, n, S): the op as it takes a channel's decay of one channel,
# against the same decay broadcast over d_k and the recurrence, keys
# and values of different widths

SCALAR = dict(b=1, n=2, s=128, dk=16, dv=32)


def _scalar_inputs(seed, **shape):
    """`_inputs` with one log-decay a head and token, beta in (0, 2)."""
    args, do = _inputs(seed, **{**SCALAR, **shape})
    g = -2.0 * jax.nn.softplus(jax.random.normal(
        jax.random.PRNGKey(seed + 100), args[4].shape) - 2)
    return args[:3] + (g,) + args[4:], do


@pytest.fixture(scope="module")
def scalar_runs():
    """{resets: (a head's decay, the recurrence, the channel decay
    broadcast inside the differentiated function)}, each (o, dq, dk,
    dv, dg, dbeta), dg of (B, n, S); the compiled stage (the kernels are
    held to it below, at 96 / 192)."""
    out = {}
    for resets in (False, True):
        args, do = _scalar_inputs(5 + resets)
        first = _resets(1, 128, (0, 17, 64, 100)) if resets else None
        rule = lambda *a: DR.gated_delta_rule(*a, resets=first, chunk=32)
        out[resets] = (
            _fwd_bwd(rule, args, do),
            _fwd_bwd(lambda *a: DR.gated_delta_rule_reference(
                *a, resets=first), args, do),
            _fwd_bwd(lambda q, k, v, g, beta: rule(
                q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta),
                args, do))
    return out


@pytest.mark.parametrize("what", range(6), ids=NAMES)
@pytest.mark.parametrize("resets", [False, True], ids=["row", "documents"])
def test_a_heads_decay_is_the_channels_broadcast_and_the_recurrence(
        scalar_runs, resets, what):
    """float32: the (C, C) decay matrix against the token recurrence
    (with resets, its exact zero against the pinned decay) and against
    the per-channel pair products over the same decay broadcast; the
    bands of the per-channel tests above."""
    got, want, chan = scalar_runs[resets]
    assert got[what].shape == want[what].shape
    scale = float(jnp.max(jnp.abs(want[what])))
    for other in (want, chan):
        np.testing.assert_allclose(got[what], other[what], rtol=1e-4,
                                   atol=2e-5 * max(scale, 1.0))


@pytest.fixture(scope="module")
def narrow_stages():
    """(kernels, jax.numpy): `_Locals` and the five gradients of a
    head's decay with keys 96 and values 192 wide, bf16, chunk 32."""
    args, _ = _scalar_inputs(21, n=1, s=64, dk=96, dv=192,
                             dtype=jnp.bfloat16)
    args = args[:3] + (args[3][..., None],) + args[4:]

    def run(stage, args):
        loc, pull = jax.vjp(lambda *a: stage(*a, 32), *args)
        keys = jax.random.split(jax.random.PRNGKey(9), len(loc))
        cot = DR._Locals(*(jax.random.normal(k, x.shape).astype(x.dtype)
                           for k, x in zip(keys, loc)))
        return tuple(loc), pull(cot)
    return (jax.jit(functools.partial(run, DR._locals_kernels))(args),
            jax.jit(functools.partial(run, DR._locals))(args))


@pytest.mark.parametrize("what", range(11), ids=LOCALS + GRADS)
def test_96_and_192_wide_heads_through_the_pallas_pair(narrow_stages, what):
    """The pair interpreted at Gated DeltaNet's widths against the
    compiled stage, the six of `_Locals` and the five gradients: the
    bf16 band of the 128-wide tests above."""
    (loc, grads), (want_loc, want_grads) = narrow_stages
    got, want = (loc + grads)[what], (want_loc + want_grads)[what]
    assert got.dtype == want.dtype and got.shape == want.shape
    _close(got, want, 8e-3)


def test_the_counters_of_a_heads_decay():
    """`scalar_calls` and `scalar_kernel_calls` count the calls with a
    head's decay and those of them on the pair; the state's elements a
    head against those of the (8, 128) tiles the float32 state lies
    in: 192 values take two tiles of lanes, 75% of them carry."""
    args, _ = _scalar_inputs(3, dk=96, dv=192)
    DR.reset_stats()
    jax.eval_shape(lambda *a: DR.gated_delta_rule(
        *a, chunk=32, use_pallas_override=True), *args)
    jax.eval_shape(lambda *a: DR.gated_delta_rule(*a, chunk=32), *args)
    wide = args[:3] + (jnp.broadcast_to(args[3][..., None],
                                        args[0].shape),) + args[4:]
    jax.eval_shape(lambda *a: DR.gated_delta_rule(*a, chunk=32), *wide)
    stats = DR.stats()
    assert (stats["calls"], stats["kernel_calls"]) == (3, 1)
    assert (stats["scalar_calls"], stats["scalar_kernel_calls"]) == (2, 1)
    assert stats["state_elems"] == 3 * 96 * 192
    assert stats["state_lane_elems"] == 3 * 96 * 256
    DR.reset_stats()


def test_a_decay_of_neither_shape_is_refused():
    args, _ = _scalar_inputs(4)
    with pytest.raises(ValueError, match="shapes"):
        DR.gated_delta_rule(*args[:3], args[3][:, :1], args[4], chunk=32)
