"""Flash attention parity ≡ apex/contrib/test/fmha/test_fmha.py and the
multihead_attn numerics tests: Pallas blockwise kernel vs plain softmax
attention, fwd + grads, causal and full, multiple shapes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.flash_attention import attention_reference, flash_attention


def _qkv(b, h, sq, sk, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, h, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, h, sk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 2, 32, 32, 16), (2, 1, 64, 64, 8)])
def test_flash_forward(shape, causal):
    b, h, sq, sk, d = shape
    q, k, v = _qkv(b, h, sq, sk, d)
    got = flash_attention(q, k, v, causal=causal, use_pallas_override=True)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_cross_attention_shapes():
    # sq != sk (encdec ≡ fast_multihead_attn encdec variants)
    q, k, v = _qkv(1, 2, 32, 64, 16, seed=1)
    got = flash_attention(q, k, v, causal=False, use_pallas_override=True)
    want = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads(causal):
    q, k, v = _qkv(1, 2, 32, 32, 16, seed=2)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, use_pallas_override=True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(attention_reference(q, k, v, causal=causal)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_bf16():
    q, k, v = _qkv(1, 2, 64, 64, 32, jnp.bfloat16, seed=3)
    got = flash_attention(q, k, v, causal=True, use_pallas_override=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_flash_long_seq_blocks():
    # multiple q/k blocks (seq 256 → blocks of 256? no: picks 256; use 160
    # to force 32-blocks... 160 % 32 == 0)
    q, k, v = _qkv(1, 1, 160, 160, 8, seed=4)
    got = flash_attention(q, k, v, causal=True, use_pallas_override=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_dropout_fallback_api():
    """dropout on the non-kernel path: masks attention weights, scales
    by 1/keep, deterministic per key, E[out] tracks the no-dropout
    output (the in-kernel coordinate-hash mask has its own test,
    test_flash_in_kernel_dropout_mask_consistency)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest
    from apex_tpu.ops.flash_attention import flash_attention

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 64, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 64, 32))
    with pytest.raises(ValueError, match="dropout_key"):
        flash_attention(q, k, v, dropout_rate=0.1)
    key = jax.random.PRNGKey(3)
    o1 = flash_attention(q, k, v, causal=True, dropout_rate=0.3,
                         dropout_key=key)
    o2 = flash_attention(q, k, v, causal=True, dropout_rate=0.3,
                         dropout_key=key)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    base = np.asarray(flash_attention(q, k, v, causal=True))
    acc = np.zeros_like(base)
    n = 32
    for i in range(n):
        acc += np.asarray(flash_attention(
            q, k, v, causal=True, dropout_rate=0.3,
            dropout_key=jax.random.PRNGKey(50 + i)))
    rel = np.abs(acc / n - base).mean() / np.abs(base).mean()
    assert rel < 0.3, rel


# ---------------- masks / bias / varlen (round 2: VERDICT missing #1-2) -----

@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_mask_parity(causal):
    """Segment ids ≡ the reference's padding/attention masks
    (multihead_attn mask paths) and fmha varlen cu_seqlens."""
    b, h, s, d = 2, 2, 64, 16
    q, k, v = _qkv(b, h, s, s, d, seed=3)
    # two packed segments + a pad tail per row
    seg = jnp.stack([
        jnp.concatenate([jnp.zeros(24, jnp.int32), jnp.ones(24, jnp.int32),
                         jnp.full((16,), 7, jnp.int32)]),
        jnp.concatenate([jnp.zeros(40, jnp.int32),
                         jnp.full((24,), 3, jnp.int32)]),
    ])
    got = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                          use_pallas_override=True)
    want = attention_reference(q, k, v, causal=causal,
                               q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_segment_grads():
    b, h, s, d = 1, 2, 64, 16
    q, k, v = _qkv(b, h, s, s, d, seed=4)
    seg = jnp.concatenate([jnp.zeros(32, jnp.int32),
                           jnp.ones(32, jnp.int32)])[None, :]

    def lf(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, segment_ids=seg, use_pallas_override=True)))

    def lr(q, k, v):
        return jnp.sum(jnp.sin(attention_reference(
            q, k, v, q_segment_ids=seg, kv_segment_ids=seg)))

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, e, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_varlen_packing_equivalence():
    """Two sequences packed into one row with distinct segment ids give
    the same outputs as attending to each separately — the capability
    fmha's cu_seqlens packing provides (fmha_api.cpp:18-160)."""
    h, d = 2, 16
    s1, s2 = 24, 40
    q, k, v = _qkv(1, h, s1 + s2, s1 + s2, d, seed=5)
    seg = jnp.concatenate([jnp.zeros(s1, jnp.int32),
                           jnp.ones(s2, jnp.int32)])[None, :]
    packed = flash_attention(q, k, v, causal=True, segment_ids=seg,
                             use_pallas_override=True)
    sep1 = attention_reference(q[:, :, :s1], k[:, :, :s1], v[:, :, :s1],
                               causal=True)
    sep2 = attention_reference(q[:, :, s1:], k[:, :, s1:], v[:, :, s1:],
                               causal=True)
    np.testing.assert_allclose(np.asarray(packed[:, :, :s1]),
                               np.asarray(sep1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(packed[:, :, s1:]),
                               np.asarray(sep2), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bias_shape", [(1, 1), (1, 2), (2, 1), (2, 2)])
def test_flash_additive_bias_parity(bias_shape):
    """Additive score bias ≡ the fused x*scale + mask softmax
    (multihead_attn/softmax.cuh:27-200); covers ALiBi/rel-pos masks."""
    b, h, s, d = 2, 2, 64, 16
    q, k, v = _qkv(b, h, s, s, d, seed=6)
    nb, nh = bias_shape
    bias = jax.random.normal(jax.random.PRNGKey(9), (nb, nh, s, s),
                             jnp.float32)
    got = flash_attention(q, k, v, bias=bias, use_pallas_override=True)
    want = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_bias_grads_qkv():
    """q/k/v grads flow through a bias; bias_grad=False keeps the
    constant-bias zero-cotangent contract."""
    b, h, s, d = 1, 2, 32, 16
    q, k, v = _qkv(b, h, s, s, d, seed=7)
    bias = jax.random.normal(jax.random.PRNGKey(8), (1, h, s, s))

    def lf(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, bias=bias, use_pallas_override=True)))

    def lr(q, k, v):
        return jnp.sum(jnp.sin(attention_reference(q, k, v, bias=bias)))

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, e, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"d{name} mismatch")
    dbias = jax.grad(lambda bb: jnp.sum(flash_attention(
        q, k, v, bias=bb, bias_grad=False,
        use_pallas_override=True)))(bias)
    assert float(jnp.max(jnp.abs(dbias))) == 0.0


# ------------------ trainable bias (round 4: VERDICT missing #1) ------------

@pytest.mark.parametrize("bias_shape", [(1, 1), (1, 2), (2, 1), (2, 2)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_dbias_full_parity(bias_shape, causal):
    """Trainable full (sq, sk) bias: kernel dbias ≡ dense AD, including
    the broadcast-dim reductions (≡ self_multihead_attn_bias.cu
    capability — bias trains end-to-end on the fast path)."""
    b, h, s, d = 2, 2, 32, 16
    q, k, v = _qkv(b, h, s, s, d, seed=13)
    nb, nh = bias_shape
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(14), (nb, nh, s, s))

    def lf(bb):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, bias=bb, causal=causal, use_pallas_override=True)))

    def lr(bb):
        return jnp.sum(jnp.sin(attention_reference(
            q, k, v, bias=bb, causal=causal)))

    got, want = jax.grad(lf)(bias), jax.grad(lr)(bias)
    assert got.shape == bias.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("bias_shape", [(1, 1), (2, 2)])
def test_flash_dbias_sk_compact_parity(bias_shape):
    """Trainable key-compact (.., 1, sk) bias (learned ALiBi / padding
    shape): the in-kernel q-sum dbias ≡ dense AD — and the forward
    never expands it to sq x sk."""
    b, h, s, d = 2, 2, 32, 16
    q, k, v = _qkv(b, h, s, s, d, seed=15)
    nb, nh = bias_shape
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(16), (nb, nh, 1, s))

    def lf(bb):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, bias=bb, use_pallas_override=True)))

    def lr(bb):
        return jnp.sum(jnp.sin(attention_reference(q, k, v, bias=bb)))

    got, want = jax.grad(lf)(bias), jax.grad(lr)(bias)
    assert got.shape == bias.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
    # forward parity through the native compact path too
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, bias=bias,
                                   use_pallas_override=True)),
        np.asarray(attention_reference(q, k, v, bias=bias)),
        rtol=1e-4, atol=1e-4)


def test_flash_dbias_query_compact_zero():
    """A (.., sq, 1) bias adds a per-query constant — softmax cancels
    it: gradient is EXACTLY zero (dense AD agrees to float eps)."""
    b, h, s, d = 1, 2, 32, 16
    q, k, v = _qkv(b, h, s, s, d, seed=17)
    bias = jax.random.normal(jax.random.PRNGKey(18), (1, h, s, 1))
    got = jax.grad(lambda bb: jnp.sum(jnp.sin(flash_attention(
        q, k, v, bias=bb, use_pallas_override=True))))(bias)
    assert float(jnp.max(jnp.abs(got))) == 0.0
    want = jax.grad(lambda bb: jnp.sum(jnp.sin(attention_reference(
        q, k, v, bias=bb))))(bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


def test_flash_dbias_two_kernel_path(monkeypatch):
    """Force the long-context two-kernel backward (dq-kernel dbias
    blocks) by shrinking the fused-path cap."""
    from apex_tpu.ops import flash_attention as FA
    monkeypatch.setattr(FA, "_FUSED_BWD_CAP", 1)
    b, h, s, d = 1, 2, 64, 16
    q, k, v = _qkv(b, h, s, s, d, seed=19)
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(20), (1, h, s, s))

    def lf(q, k, v, bb):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, bias=bb, causal=True, use_pallas_override=True)))

    def lr(q, k, v, bb):
        return jnp.sum(jnp.sin(attention_reference(
            q, k, v, bias=bb, causal=True)))

    g1 = jax.grad(lf, argnums=(0, 1, 2, 3))(q, k, v, bias)
    g2 = jax.grad(lr, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, e, name in zip(g1, g2, ("q", "k", "v", "bias")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_bias_with_segments_and_causal():
    b, h, s, d = 1, 2, 64, 16
    q, k, v = _qkv(b, h, s, s, d, seed=10)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(11), (1, 1, s, s))
    seg = jnp.concatenate([jnp.zeros(48, jnp.int32),
                           jnp.ones(16, jnp.int32)])[None, :]
    got = flash_attention(q, k, v, causal=True, bias=bias, segment_ids=seg,
                          use_pallas_override=True)
    want = attention_reference(q, k, v, causal=True, bias=bias,
                               q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_segment_api_validation():
    q, k, v = _qkv(1, 1, 32, 32, 8)
    seg = jnp.zeros((1, 32), jnp.int32)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, segment_ids=seg, q_segment_ids=seg,
                        kv_segment_ids=seg)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, q_segment_ids=seg)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, bias=jnp.zeros((3, 1, 32, 32)))


def test_flash_in_kernel_dropout_mask_consistency():
    """The in-kernel dropout mask is a pure coordinate hash, so
    interpret mode reproduces the TPU masks bit-for-bit and fwd/bwd
    must agree: with a fixed mask the output is LINEAR in v, making
    directional finite differences exact (this was unverifiable in CPU
    CI with the hardware PRNG — whose stream order even differed
    between the fwd and fused-bwd kernels)."""
    from apex_tpu.ops.flash_attention import _flash
    B, H, S, D = 1, 2, 128, 32
    qq = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, D))
    kk = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D))
    vv = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D))
    cc = jax.random.normal(jax.random.PRNGKey(3), (B, H, S, D))
    seed = jnp.asarray([[777]], jnp.int32)
    from apex_tpu.ops.flash_attention import _kernel_shape
    shape = _kernel_shape(S, S, D, D, qq.dtype, True)
    # (bias, q_seg, kv_seg, scale, causal, rate, shape, bias_grad, seed)
    args = (None, None, None, 0.18, True, 0.2, shape, False, seed)
    o1 = np.asarray(_flash(qq, kk, vv, *args))
    o2 = np.asarray(_flash(qq, kk, vv, *args))
    np.testing.assert_array_equal(o1, o2)

    def f(v_):
        return jnp.vdot(_flash(qq, kk, v_, *args), cc)

    gv = jax.grad(f)(vv)
    dirv = jax.random.normal(jax.random.PRNGKey(4), vv.shape)
    fd = float(f(vv + 0.5 * dirv)) - float(f(vv - 0.5 * dirv))
    an = float(jnp.vdot(gv, dirv))
    assert abs(fd - an) < 1e-3 * abs(an) + 1e-4, (fd, an)

    # keep-rate statistic ~ 1 - rate
    p_nodrop = np.asarray(_flash(
        qq, kk, vv, None, None, None, 0.18, True, 0.0, shape, False, seed))
    assert not np.allclose(o1, p_nodrop)


# ----------------- the packed projection's own layout ----------------------

from apex_tpu.ops import flash_attention as flash_mod  # noqa: E402
from apex_tpu.ops.flash_attention import flash_attention_qkv  # noqa: E402
from apex_tpu.ops.fused_dense import qkv_split_heads  # noqa: E402

# (S, B, heads, head_dim, block_q, block_k): head pairs at d = 64, one
# head a lane block at d = 128, four at d = 32; one block and several
_QKV_SHAPES = {
    "d64-one-block": (64, 2, 4, 64, None, None),
    "d64-blocks": (128, 2, 2, 64, 32, 64),
    "d128-one-block": (64, 2, 2, 128, None, None),
    "d128-blocks": (128, 1, 2, 128, 64, 32),
    "d32-blocks": (64, 1, 8, 32, 32, 16),
}


def _layouts(stats):
    """The calls by layout, without the score counters beside them."""
    return {k: stats[k] for k in ("projection_layout", "head_major")}


def _packed(s, b, nh, d, dtype, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    qkv = jax.random.normal(k1, (s, b, 3 * nh * d), jnp.float32)
    weight = jax.random.normal(k2, (s, b, nh * d), jnp.float32)
    return qkv.astype(dtype), weight


def _split_then(attn, qkv, nh, d, **kw):
    """The head-major way to the same (S, B, H) context."""
    s, b, _ = qkv.shape
    ctx = attn(*qkv_split_heads(qkv, nh, d), **kw)
    return ctx.transpose(2, 0, 1, 3).reshape(s, b, nh * d)


def _loss_and_grad(attn, qkv, weight):
    def loss(x):
        return jnp.sum(attn(x).astype(jnp.float32) * weight)
    out = attn(qkv)
    return np.asarray(out, np.float32), np.asarray(
        jax.grad(loss)(qkv), np.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("shape", list(_QKV_SHAPES))
def test_flash_qkv_matches_reference_and_head_major(shape, causal, dtype):
    """Forward and d(projection) — dq, dk and dv side by side — of the
    projection-layout entry against the dense reference and against the
    head-major kernel on the same values."""
    s, b, nh, d, bq, bk = _QKV_SHAPES[shape]
    qkv, weight = _packed(s, b, nh, d, dtype)
    flash_mod.reset_stats()
    got = _loss_and_grad(
        lambda x: flash_attention_qkv(
            x, nh, causal=causal, block_q=bq, block_k=bk,
            use_pallas_override=True), qkv, weight)
    assert _layouts(flash_mod.stats()) == {"projection_layout": 2, "head_major": 0}
    kernel = _loss_and_grad(
        lambda x: _split_then(flash_attention, x, nh, d, causal=causal,
                              block_q=bq, block_k=bk,
                              use_pallas_override=True), qkv, weight)
    dense = _loss_and_grad(
        lambda x: _split_then(attention_reference, x, nh, d, causal=causal),
        qkv, weight)
    # the arithmetic of a head is the head-major kernel's, to the order
    # of the sums in P @ V and in rowsum(dO * O)
    tight = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    loose = dict(rtol=2e-3, atol=2e-3) if dtype == jnp.float32 else dict(
        rtol=5e-2, atol=5e-2)
    for a, e, what in zip(got, kernel, ("context", "d(projection)")):
        np.testing.assert_allclose(a, e, err_msg=what, **tight)
    for a, e, what in zip(got, dense, ("context", "d(projection)")):
        np.testing.assert_allclose(a, e, err_msg=what, **loose)


@pytest.mark.parametrize("shape", ["d64-blocks", "d128-one-block"])
def test_flash_qkv_dropout_keeps_what_head_major_keeps(shape):
    """One seed, one keep-mask for (batch, head, q, k) in both layouts:
    outputs and gradients equal to rounding (a kept weight that one
    layout dropped would be an error of the weight's own size)."""
    s, b, nh, d, bq, bk = _QKV_SHAPES[shape]
    qkv, weight = _packed(s, b, nh, d, jnp.float32, seed=3)
    kw = dict(causal=True, dropout_rate=0.25,
              dropout_key=jax.random.PRNGKey(11), block_q=bq, block_k=bk,
              use_pallas_override=True)
    got = _loss_and_grad(lambda x: flash_attention_qkv(x, nh, **kw),
                         qkv, weight)
    want = _loss_and_grad(
        lambda x: _split_then(flash_attention, x, nh, d, **kw), qkv, weight)
    plain = flash_attention_qkv(qkv, nh, causal=True, block_q=bq,
                                block_k=bk, use_pallas_override=True)
    assert not np.allclose(got[0], np.asarray(plain))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s,nh,d,why", [
    (64, 3, 64, "an odd head count at d = 64: 192 lanes"),
    (64, 2, 48, "128 % d != 0"),
    (64, 1, 256, "a head wider than a lane block"),
    (8192, 2, 64, "a sequence whose fused backward does not fit VMEM"),
])
def test_flash_qkv_falls_back_to_head_major(s, nh, d, why, monkeypatch):
    """A shape the lane blocks cannot address takes the split, the
    transposes and the head-major kernel, is counted as such, and gives
    the same numbers."""
    if s > 1024:    # count the decision; do not run 8192 interpreted
        monkeypatch.setattr(flash_mod, "_flash",
                            lambda q, *a: jnp.zeros_like(q))
    qkv, weight = _packed(s, 1, nh, d, jnp.float32, seed=5)
    flash_mod.reset_stats()
    got = flash_attention_qkv(qkv, nh, causal=True,
                              use_pallas_override=True)
    assert _layouts(flash_mod.stats()) == {"projection_layout": 0, "head_major": 1}, why
    assert got.shape == (s, 1, nh * d)
    if s <= 1024:
        want = _split_then(attention_reference, qkv, nh, d, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_flash_qkv_without_kernels_is_neither():
    """Off the kernels (the jnp reference) a call is counted as neither
    layout, and the entry validates like `flash_attention`."""
    qkv, _ = _packed(32, 1, 2, 64, jnp.float32)
    flash_mod.reset_stats()
    got = flash_attention_qkv(qkv, 2, causal=True, use_pallas_override=False)
    want = _split_then(attention_reference, qkv, 2, 64, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert _layouts(flash_mod.stats()) == {"projection_layout": 0, "head_major": 0}
    with pytest.raises(ValueError):
        flash_attention_qkv(qkv, 5)
    with pytest.raises(ValueError):
        flash_attention_qkv(qkv, 2, dropout_rate=0.1)


# --------------- causal compute tiles inside a block ------------------------
#
# A causal call cuts the block a step is handed into static windows
# (`_pick_tile`, `_tile_cases`, `_tile_dispatch`): a q window runs
# against the k rows its queries see and no others, under the mask
# where the diagonal crosses them.  The rule is patched to 128 rows
# here so that several tiles run at S = 256-512.

@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(
        flash_mod, "_pick_tile",
        lambda bq, backward: 128 if bq % 128 == 0 else bq)


def _fwd_and_grads(attn, *operands, seed=9):
    out, vjp = jax.vjp(attn, *operands)
    do = jax.random.normal(jax.random.PRNGKey(seed), out.shape, out.dtype)
    return (out,) + vjp(do)


def _two_widths(b, h, s, d, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, h, s, d)),
            jax.random.normal(ks[1], (b, h, s, d)),
            jax.random.normal(ks[2], (b, h, s, dv)))


# one block a head: the tiles are chosen while tracing; (256, 128) blocks
# over 512: a block crosses the diagonal at delta 0 and at -128, lies
# below it, or above it
_TILED_BLOCKS = {"one_block": (None, None), "bq_2bk": (256, 128)}


@pytest.mark.parametrize("blocks", list(_TILED_BLOCKS))
@pytest.mark.parametrize("d,dv", [(64, 64), (128, 128), (192, 128)],
                         ids=["d64", "d128", "k192_v128"])
def test_causal_tiles_head_major(d, dv, blocks, small_tiles):
    """Forward and all three gradients of the tiled kernels against the
    dense reference."""
    bq, bk = _TILED_BLOCKS[blocks]
    q, k, v = _two_widths(1, 2, 512, d, dv)
    got = _fwd_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk,
        use_pallas_override=True), q, k, v)
    want = _fwd_and_grads(lambda q, k, v: attention_reference(
        q, k, v, causal=True), q, k, v)
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("blocks", list(_TILED_BLOCKS))
def test_causal_tiles_projection_layout(blocks, small_tiles):
    """The same in the projection's layout: two heads of 64 a lane
    block, the statistics and accumulators at a window of lanes, v
    transposed once a step and read at the window's columns."""
    bq, bk = _TILED_BLOCKS[blocks]
    s, b, nh, d = 512, 2, 4, 64
    qkv, weight = _packed(s, b, nh, d, jnp.float32, seed=7)
    flash_mod.reset_stats()
    got = _loss_and_grad(lambda x: flash_attention_qkv(
        x, nh, causal=True, block_q=bq, block_k=bk,
        use_pallas_override=True), qkv, weight)
    want = _loss_and_grad(
        lambda x: _split_then(attention_reference, x, nh, d, causal=True),
        qkv, weight)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=2e-5)
    # three kernels traced (a forward, and a forward and a backward
    # under grad), each over the 10 of a 512 square's 16 tiles of 128
    # that lie on or under the diagonal, however the blocks cut it
    took = flash_mod.stats()
    assert took["scores_computed"] == 3 * b * nh * 10 * 128 * 128
    assert took["scores_required"] == 3 * b * nh * 512 * 513 // 2


@pytest.mark.parametrize("blocks", list(_TILED_BLOCKS))
def test_causal_tiles_with_segment_ids(blocks, small_tiles):
    """Packed sequences: the segment rows are read at the window."""
    bq, bk = _TILED_BLOCKS[blocks]
    q, k, v = _two_widths(2, 2, 512, 64, 64, seed=1)
    seg = jnp.stack([jnp.repeat(jnp.arange(4), 128),
                     (jnp.arange(512) >= 200).astype(jnp.int32)])
    got = _fwd_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, segment_ids=seg, block_q=bq, block_k=bk,
        use_pallas_override=True), q, k, v)
    want = _fwd_and_grads(lambda q, k, v: attention_reference(
        q, k, v, causal=True, q_segment_ids=seg, kv_segment_ids=seg),
        q, k, v)
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("bias_grad", [False, True])
def test_causal_tiles_with_a_key_compact_bias(bias_grad, small_tiles):
    """A (.., 1, sk) bias rides as a row and is read at the k window;
    wanted, its gradient sends the backward to the two kernels, which
    the tiles leave alone."""
    q, k, v = _two_widths(1, 2, 256, 64, 64, seed=2)
    bias = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 1, 256))
    got = _fwd_and_grads(lambda q, k, v, bias: flash_attention(
        q, k, v, causal=True, bias=bias, bias_grad=bias_grad,
        use_pallas_override=True), q, k, v, bias)
    want = _fwd_and_grads(lambda q, k, v, bias: attention_reference(
        q, k, v, causal=True, bias=bias), q, k, v, bias)
    for g, w, name in zip(got[:4], want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)
    np.testing.assert_allclose(got[4], want[4] if bias_grad else 0.0,
                               rtol=2e-5, atol=2e-5)


def test_causal_tiles_with_a_full_bias_and_its_gradient(small_tiles):
    """A (sq, sk) bias is read at the tile's window of its block, and
    its gradient, ds itself, written there: a tile that never runs
    leaves the zeros the step began with."""
    q, k, v = _two_widths(1, 2, 256, 64, 64, seed=3)
    bias = jax.random.normal(jax.random.PRNGKey(6), (1, 2, 256, 256))
    got = _fwd_and_grads(lambda q, k, v, bias: flash_attention(
        q, k, v, causal=True, bias=bias, use_pallas_override=True),
        q, k, v, bias)
    want = _fwd_and_grads(lambda q, k, v, bias: attention_reference(
        q, k, v, causal=True, bias=bias), q, k, v, bias)
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv", "dbias")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("blocks", list(_TILED_BLOCKS))
def test_causal_tiles_drop_what_the_dense_mask_drops(blocks, small_tiles):
    """Dropout 0.1: a tile's keep-mask is its window of
    `dropout_keep_dense`'s bits, forward and backward."""
    bq, bk = _TILED_BLOCKS[blocks]
    rate, key = 0.1, jax.random.PRNGKey(21)
    q, k, v = _two_widths(1, 2, 512, 64, 64, seed=4)
    keep = flash_mod.dropout_keep_dense(
        flash_mod._dropout_seed(rate, key), 1, 2, 512, 512, rate)

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 8.0
        s = jnp.where(jnp.triu(jnp.ones((512, 512), bool), k=1), -1e30, s)
        p = jnp.where(keep, jax.nn.softmax(s, axis=-1), 0.0) / (1.0 - rate)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    got = _fwd_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, dropout_rate=rate, dropout_key=key,
        block_q=bq, block_k=bk, use_pallas_override=True), q, k, v)
    want = _fwd_and_grads(dense, q, k, v)
    assert 0.05 < 1.0 - float(jnp.mean(keep)) < 0.15
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)


def _kernel_texts(fn, *operands):
    """name -> text of each pallas_call's kernel jaxpr in fn's jaxpr."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = str(eqn.params["jaxpr"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*operands).jaxpr)
    return found


@pytest.mark.parametrize("layout", ["head_major", "projection_layout"])
def test_no_tile_above_the_diagonal_is_emitted(layout, small_tiles):
    """One block a head of 512 in q windows of 128: the kernels are a
    straight line of 4 tiles, a window's 128 queries against the 128,
    256, 384 and 512 keys they see, each under its mask; the k rows
    above the diagonal (6 of the 16 tiles of 128) are not in the
    program and nothing branches.  Without a mask the program is one
    tile, as it was."""
    def traced(causal):
        if layout == "head_major":
            q = jnp.zeros((1, 2, 512, 64), jnp.float32)
            return _kernel_texts(lambda q: jax.vjp(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, use_pallas_override=True),
                q, q, q)[1](q), q)
        x = jnp.zeros((512, 1, 3 * 2 * 64), jnp.float32)
        return _kernel_texts(lambda x: jax.vjp(
            lambda x: flash_attention_qkv(
                x, 2, causal=causal, use_pallas_override=True),
            x)[1](x[..., :128]), x)

    tiled, whole = traced(True), traced(False)
    assert set(tiled) == set(whole) == {"flash_fwd", "flash_bwd"}
    # matmuls a tile: QK^T and P.V forward; QK^T, dO.V^T, dV, dK, dQ back
    for name, per_tile in (("flash_fwd", 2), ("flash_bwd", 5)):
        assert whole[name].count("dot_general") == per_tile
        assert tiled[name].count("dot_general") == 4 * per_tile
        assert "f32[512,512]" in whole[name]
        # score tiles, (k rows, q rows)
        shapes = re.findall(r"f32\[(\d+),128\] = dot_general", tiled[name])
        assert set(shapes) >= {"128", "256", "384", "512"}
        assert "f32[512,512]" not in tiled[name]
        # the mask: one `where` a tile, and no branch picks tiles
        assert tiled[name].count("name=_where") == 4
        assert whole[name].count("name=_where") == 0
        assert tiled[name].count("cond[") == whole[name].count("cond[")


# ------------------- the kernel shape of a call, decided once ---------------
#
# `_kernel_shape` is the one place the blocks, the causal compute tile of
# each direction and the kind of backward are chosen; the forward and
# the backward of a call are built from the one value it returns.  The
# values below are what the kernels of each benchmark cell ran with
# before the decision had one place (PERF.md, PR 29's lines), and the
# two corners no cell reaches.

# layout, its shape, dtype -> (bq, bk, tile_fwd, tile_bwd, fused_bwd);
# "qkv": (S, B, heads, d) of the projection; "bhsd": (B, heads, S, d, dv)
_DECISIONS = {
    "gpt2_medium_b12s1024": ("qkv", (1024, 12, 16, 64), jnp.bfloat16,
                             (1024, 1024, 512, 256, True)),
    "gpt_1p3b_b7s512": ("qkv", (512, 7, 32, 64), jnp.bfloat16,
                        (512, 512, 512, 256, True)),
    "gpt_1p3b_tp2dp2_b8s1024": ("qkv", (1024, 8, 16, 64), jnp.bfloat16,
                                (1024, 1024, 512, 256, True)),
    # the committed v5e entry: (1024, 512) and the single pass, where
    # the heuristics say (512, 1024) and the cap says two kernels
    "joyai_b2s4096_192_128": ("bhsd", (2, 32, 4096, 192, 128), jnp.bfloat16,
                              (1024, 512, 512, 256, True)),
    # past the cap: two kernels, each computing a block in one piece
    "past_the_cap_s8192": ("bhsd", (1, 2, 8192, 64, 64), jnp.bfloat16,
                           (512, 1024, 512, 512, False)),
    # a 128-lane fp32 block is twice a bf16 one: the q block is halved
    "fp32_projection_s1024": ("qkv", (1024, 2, 4, 64), jnp.float32,
                              (512, 1024, 512, 256, True)),
}


@pytest.mark.parametrize("case", list(_DECISIONS))
def test_the_kernel_shape_is_decided_once_a_call(case, monkeypatch,
                                                 tmp_path):
    from apex_tpu import tune

    layout, dims, dtype, want = _DECISIONS[case]
    # the tuner as a v5e sees it: the committed defaults, no user file
    monkeypatch.setenv(tune.ENV_CACHE_PATH, str(tmp_path / "tune.json"))
    monkeypatch.setattr(tune.cache, "device_kind", lambda: "v5e")
    tune.invalidate()
    tune.reset_stats()
    decided, built = [], []
    decide, count = flash_mod._kernel_shape, flash_mod._count_scores

    def deciding(*a, **kw):
        decided.append(decide(*a, **kw))
        return decided[-1]

    def counting(heads, sq, sk, bq, bk, causal, tile, passes=1):
        built.append((bq, bk, tile, passes))
        return count(heads, sq, sk, bq, bk, causal, tile, passes)

    monkeypatch.setattr(flash_mod, "_kernel_shape", deciding)
    monkeypatch.setattr(flash_mod, "_count_scores", counting)
    if layout == "qkv":
        s, b, nh, d = dims
        operands = [jax.ShapeDtypeStruct((s, b, 3 * nh * d), dtype)]

        def attn(x):
            return flash_attention_qkv(x, nh, causal=True,
                                       use_pallas_override=True)
    else:
        b, nh, s, d, dv = dims
        qk = jax.ShapeDtypeStruct((b, nh, s, d), dtype)
        operands = [qk, qk, jax.ShapeDtypeStruct((b, nh, s, dv), dtype)]

        def attn(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   use_pallas_override=True)

    def fwd_bwd(*xs):
        out, vjp = jax.vjp(attn, *xs)
        return vjp(out)

    try:
        jax.eval_shape(fwd_bwd, *operands)
    finally:
        tune.invalidate()
    # one decision and one tuner lookup a public call, and the forward
    # and the backward were both built from it
    assert decided == [want]
    lookups = tune.stats()
    assert lookups["hits"] + lookups["misses"] == 1
    assert lookups["hits"] == (case == "joyai_b2s4096_192_128")
    bq, bk, tile_fwd, tile_bwd, fused = want
    assert built == [(bq, bk, tile_fwd, 1),
                     (bq, bk, tile_bwd, 1 if fused else 2)]
