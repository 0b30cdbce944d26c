"""Ring attention / Ulysses vs dense attention — the long-context CP
layer (beyond reference parity; SURVEY §2.4 CP note)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.flash_attention import attention_reference
from apex_tpu.parallel import mesh as M
from apex_tpu.parallel.context_parallel import (
    ring_attention,
    ulysses_attention,
)

N = 8


def _qkv(b, h, s, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, h, s, d)),
            jax.random.normal(ks[1], (b, h, s, d)),
            jax.random.normal(ks[2], (b, h, s, d)))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(1, 2, 64, 16)

    f = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "tp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "tp"), P(None, None, "tp"),
                  P(None, None, "tp")),
        out_specs=P(None, None, "tp"), check_vma=False)
    got = f(q, k, v)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ring_attention_grads():
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(1, 1, 32, 8, seed=1)

    def local_grads(q, k, v):
        def loss(q, k, v):
            o = ring_attention(q, k, v, "tp", causal=True)
            return jnp.sum(o ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    spec = P(None, None, "tp")
    g = shard_map(local_grads, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=(spec, spec, spec), check_vma=False)(q, k, v)
    r = jax.grad(
        lambda q, k, v: jnp.sum(attention_reference(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, e, n in zip(g, r, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"d{n}")


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(2, 8, 64, 16, seed=2)  # h=8 divisible by N

    f = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "tp", causal=causal,
                                          use_flash=False),
        mesh=mesh,
        in_specs=(P(None, None, "tp"),) * 3,
        out_specs=P(None, None, "tp"), check_vma=False)
    got = f(q, k, v)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_segment_ids(causal):
    """Packed-varlen segments with GLOBAL semantics across the ring —
    segments deliberately span shard boundaries (s_local=8, seg len 12)."""
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(2, 2, 64, 16, seed=3)
    seg = (jnp.arange(64) // 12)[None, :].repeat(2, axis=0)

    f = shard_map(
        lambda q, k, v, s: ring_attention(q, k, v, "tp", causal=causal,
                                          segment_ids=s),
        mesh=mesh,
        in_specs=(P(None, None, "tp"),) * 3 + (P(None, "tp"),),
        out_specs=P(None, None, "tp"), check_vma=False)
    got = f(q, k, v, seg)
    want = attention_reference(q, k, v, causal=causal,
                               q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ring_attention_segment_grads():
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(1, 1, 64, 16, seed=4)
    seg = (jnp.arange(64) // 24)[None, :]

    def local_grads(q, k, v, s):
        def loss(q, k, v):
            o = ring_attention(q, k, v, "tp", causal=True, segment_ids=s)
            return jnp.sum(o ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    spec = P(None, None, "tp")
    g = shard_map(local_grads, mesh=mesh,
                  in_specs=(spec,) * 3 + (P(None, "tp"),),
                  out_specs=(spec,) * 3, check_vma=False)(q, k, v, seg)
    r = jax.grad(
        lambda q, k, v: jnp.sum(attention_reference(
            q, k, v, causal=True, q_segment_ids=seg,
            kv_segment_ids=seg) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, e, n in zip(g, r, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-3, atol=1e-3, err_msg=f"d{n}")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_pallas_path(causal):
    """The TPU kernel path (interpret mode on CPU) through the ring:
    per-chunk Pallas flash fwd/bwd inside the scan/switch."""
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(1, 1, 64, 16, seed=5)

    def local_grads(q, k, v):
        def loss(q, k, v):
            o = ring_attention(q, k, v, "tp", causal=causal,
                               use_pallas_override=True)
            return jnp.sum(o ** 2)
        o = ring_attention(q, k, v, "tp", causal=causal,
                           use_pallas_override=True)
        return (o,) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    spec = P(None, None, "tp")
    o, gq, gk, gv = shard_map(local_grads, mesh=mesh,
                              in_specs=(spec,) * 3,
                              out_specs=(spec,) * 4,
                              check_vma=False)(q, k, v)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    r = jax.grad(
        lambda q, k, v: jnp.sum(attention_reference(
            q, k, v, causal=causal) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, e, n in zip((gq, gk, gv), r, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-3, atol=1e-3, err_msg=f"d{n}")


def test_ring_attention_causal_skips_chunks():
    """The causal ring must SKIP above-diagonal chunks (a lax.switch /
    HLO conditional whose skip branch does no score work), not mask
    them — check the conditional survives into the lowered HLO."""
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q = jnp.zeros((1, 1, 64, 16))

    f = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "tp", causal=True),
        mesh=mesh, in_specs=(P(None, None, "tp"),) * 3,
        out_specs=P(None, None, "tp"), check_vma=False)
    hlo = jax.jit(f).lower(q, q, q).as_text()
    # StableHLO spells the 3-way branch `stablehlo.case`
    assert "case" in hlo, "causal ring lost its skip branch"


def _ring_grad_temp_bytes(S, d=32):
    """Compiled temp size of a full ring fwd+bwd at global seq S on the
    8-way mesh — the residual-memory probe."""
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q = jnp.zeros((1, 1, S, d), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, "tp", causal=True) ** 2)

    f = shard_map(jax.grad(loss, argnums=(0, 1, 2)), mesh=mesh,
                  in_specs=(P(None, None, "tp"),) * 3,
                  out_specs=(P(None, None, "tp"),) * 3, check_vma=False)
    stats = jax.jit(f).lower(q, q, q).compile().memory_analysis()
    M.destroy_model_parallel()
    return stats.temp_size_in_bytes


def test_ring_attention_memory_linear_in_s_local():
    """custom_vjp residuals are O(s_local · d): doubling the sequence
    doubles compiled temp memory (AD-through-scan would keep
    O(n · s_local²) saved score blocks — ratio ~4 and a huge base)."""
    t16 = _ring_grad_temp_bytes(16384)
    t32 = _ring_grad_temp_bytes(32768)
    ratio = t32 / t16
    assert ratio < 2.6, (t16, t32, ratio)
    # absolute sanity: 32k tokens fwd+bwd in well under n*s_local^2
    # (8 * 4096^2 * 4B = 512 MB); measured ~55 MB
    assert t32 < 200 * 1024 * 1024, t32


def test_ring_attention_128k_causal_fwd_bwd():
    """128k-token causal fwd+bwd on the 8-way mesh (s_local = 16k).

    Parity oracle at this scale: segment ids with length 5120 (NOT a
    divisor of s_local, so segments span shard boundaries) make global
    attention block-diagonal — each segment's output and grads must
    match dense causal attention run on that segment alone.  Verifies a
    shard-interior segment and one spanning the rank0/rank1 boundary."""
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    S, d, SEG = 131072, 32, 5120
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 1, S, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1, S, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1, S, d), jnp.float32)
    seg = (jnp.arange(S) // SEG)[None, :]

    def local(q, k, v, s):
        def loss(q, k, v):
            o = ring_attention(q, k, v, "tp", causal=True, segment_ids=s)
            return jnp.sum(o ** 2)
        o = ring_attention(q, k, v, "tp", causal=True, segment_ids=s)
        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return o, gq, gk, gv

    spec = P(None, None, "tp")
    o, gq, gk, gv = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(spec,) * 3 + (P(None, "tp"),),
        out_specs=(spec,) * 4, check_vma=False))(q, k, v, seg)

    # segment 3 sits inside rank 0; segment 3*5120=15360..20480 spans
    # the 16384 rank boundary
    for g in (1, 3, 12):
        lo, hi = g * SEG, (g + 1) * SEG
        qs, ks_, vs = q[:, :, lo:hi], k[:, :, lo:hi], v[:, :, lo:hi]

        def seg_loss(qs, ks_, vs):
            return jnp.sum(attention_reference(qs, ks_, vs,
                                               causal=True) ** 2)

        want_o = attention_reference(qs, ks_, vs, causal=True)
        want_g = jax.grad(seg_loss, argnums=(0, 1, 2))(qs, ks_, vs)
        np.testing.assert_allclose(np.asarray(o[:, :, lo:hi]),
                                   np.asarray(want_o), rtol=2e-4,
                                   atol=2e-4, err_msg=f"o seg{g}")
        for a, e, nm in zip((gq, gk, gv), want_g, "qkv"):
            np.testing.assert_allclose(np.asarray(a[:, :, lo:hi]),
                                       np.asarray(e), rtol=2e-3,
                                       atol=2e-3, err_msg=f"d{nm} seg{g}")


# ---------------- zigzag (load-balanced causal) ring --------------------

def _zz_run(q, k, v, seg=None):
    from apex_tpu.parallel.context_parallel import (zigzag_shard,
                                                    zigzag_unshard)
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    qz, kz, vz = (zigzag_shard(x, N) for x in (q, k, v))
    segz = None if seg is None else zigzag_shard(seg, N, axis=1)

    def local(q, k, v, *s):
        s = s[0] if s else None

        def loss(q, k, v):
            o = ring_attention(q, k, v, "tp", causal=True,
                               layout="zigzag", segment_ids=s)
            return jnp.sum(o ** 2)

        o = ring_attention(q, k, v, "tp", causal=True, layout="zigzag",
                           segment_ids=s)
        return (o,) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    spec = P(None, None, "tp")
    in_specs = (spec,) * 3 + ((P(None, "tp"),) if seg is not None else ())
    args = (qz, kz, vz) + ((segz,) if seg is not None else ())
    o, gq, gk, gv = jax.jit(shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=(spec,) * 4,
        check_vma=False))(*args)
    return tuple(zigzag_unshard(x, N) for x in (o, gq, gk, gv))


def test_zigzag_shard_roundtrip():
    from apex_tpu.parallel.context_parallel import (zigzag_shard,
                                                    zigzag_unshard)
    x = jnp.arange(3 * 32 * 2.0).reshape(3, 1, 32, 2)
    y = zigzag_unshard(zigzag_shard(x, 8), 8)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("with_seg", [False, True])
def test_zigzag_ring_matches_dense(with_seg):
    """Load-balanced causal ring: fwd + grads ≡ dense causal attention
    (and with boundary-spanning segments)."""
    q, k, v = _qkv(1, 2, 128, 16, seed=21)
    seg = (jnp.arange(128) // 24)[None, :] if with_seg else None
    o, gq, gk, gv = _zz_run(q, k, v, seg)
    kw = ({} if seg is None
          else dict(q_segment_ids=seg, kv_segment_ids=seg))
    want = attention_reference(q, k, v, causal=True, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    r = jax.grad(
        lambda q, k, v: jnp.sum(attention_reference(
            q, k, v, causal=True, **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, e, nm in zip((gq, gk, gv), r, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"d{nm}")


def test_zigzag_ring_pallas_path():
    """Zigzag with the Pallas chunk kernels (interpret on CPU)."""
    from apex_tpu.parallel.context_parallel import (zigzag_shard,
                                                    zigzag_unshard)
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(1, 1, 128, 16, seed=22)
    qz, kz, vz = (zigzag_shard(x, N) for x in (q, k, v))

    def local(q, k, v):
        return ring_attention(q, k, v, "tp", causal=True,
                              layout="zigzag", use_pallas_override=True)

    spec = P(None, None, "tp")
    o = jax.jit(shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                          out_specs=spec, check_vma=False))(qz, kz, vz)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(zigzag_unshard(o, N)),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_segment_ids(causal, use_flash):
    # use_flash=True exercises the all_gather + flash(segment_ids=...)
    # branch; use_pallas_override=True forces the interpret-mode Pallas
    # kernel on CPU (without it flash_attention silently takes the
    # dense fallback off-TPU and the test compares the reference with
    # itself), ADVICE r4
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(2, 8, 64, 16, seed=23)
    seg = (jnp.arange(64) // 20)[None, :].repeat(2, axis=0)

    f = shard_map(
        lambda q, k, v, s: ulysses_attention(q, k, v, "tp", causal=causal,
                                             segment_ids=s,
                                             use_flash=use_flash,
                                             use_pallas_override=use_flash),
        mesh=mesh,
        in_specs=(P(None, None, "tp"),) * 3 + (P(None, "tp"),),
        out_specs=P(None, None, "tp"), check_vma=False)
    got = f(q, k, v, seg)
    want = attention_reference(q, k, v, causal=causal,
                               q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ----------------------- ring-path attention dropout --------------------


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_dropout_matches_single_chip_flash(layout, use_pallas):
    """Ring dropout uses global-coordinate hashing, so with the same
    key the ring output must EQUAL single-chip flash attention over the
    gathered sequence — forward and gradients (VERDICT r4 next-#6).
    use_pallas=False drives the jnp blockwise chunk path, whose
    dropout_keep_dense mask is bit-identical to the kernel hash."""
    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.parallel.context_parallel import (zigzag_shard,
                                                    zigzag_unshard)

    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(1, 2, 128, 16, seed=31)
    key = jax.random.PRNGKey(7)
    rate = 0.3

    def local(q, k, v):
        def loss(q, k, v):
            o = ring_attention(q, k, v, "tp", causal=True, layout=layout,
                               dropout_rate=rate, dropout_key=key,
                               use_pallas_override=use_pallas)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        o = ring_attention(q, k, v, "tp", causal=True, layout=layout,
                           dropout_rate=rate, dropout_key=key,
                           use_pallas_override=use_pallas)
        return (o,) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    spec = P(None, None, "tp")
    if layout == "zigzag":
        args = tuple(zigzag_shard(x, N) for x in (q, k, v))
    else:
        args = (q, k, v)
    o, gq, gk, gv = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 4,
        check_vma=False))(*args)
    if layout == "zigzag":
        o, gq, gk, gv = (zigzag_unshard(x, N) for x in (o, gq, gk, gv))

    # single-chip oracle with the SAME key: global-coordinate hashing
    # makes the masks identical
    def chip_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, dropout_rate=rate,
                            dropout_key=key, use_pallas_override=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    o_ref = flash_attention(q, k, v, causal=True, dropout_rate=rate,
                            dropout_key=key, use_pallas_override=True)
    g_ref = jax.grad(chip_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=2e-4, atol=2e-4)
    for a, e, nm in zip((gq, gk, gv), g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(e, np.float32),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{nm} {layout}")


def test_ring_dropout_distribution_and_jnp_path():
    """jnp (non-pallas) ring path: dropout drops ~rate of attention
    mass and is deterministic per key; fwd is reproducible."""
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=N)
    q, k, v = _qkv(1, 2, 128, 16, seed=33)
    key = jax.random.PRNGKey(9)

    def run(rate, key):
        f = shard_map(
            lambda q, k, v: ring_attention(
                q, k, v, "tp", causal=False, dropout_rate=rate,
                dropout_key=key, use_pallas_override=False),
            mesh=mesh, in_specs=(P(None, None, "tp"),) * 3,
            out_specs=P(None, None, "tp"), check_vma=False)
        return jax.jit(f)(q, k, v)

    o1 = run(0.4, key)
    o2 = run(0.4, key)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = run(0.4, jax.random.PRNGKey(10))
    assert np.abs(np.asarray(o1, np.float32)
                  - np.asarray(o3, np.float32)).max() > 1e-4
    # no dropout path unchanged by a passed key
    o4 = run(0.0, key)
    o5 = run(0.0, jax.random.PRNGKey(10))
    np.testing.assert_array_equal(np.asarray(o4), np.asarray(o5))


def test_ring_dropout_needs_key():
    q, k, v = _qkv(1, 2, 32, 8, seed=35)
    with pytest.raises(ValueError, match="dropout_key"):
        ring_attention(q, k, v, "tp", dropout_rate=0.1)
