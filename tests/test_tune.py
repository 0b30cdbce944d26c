"""Kernel autotuner (apex_tpu.tune) and the flash kernels it tunes.

Cache round-trip, corrupt/missing cache → heuristic fallback
(deterministically), device-kind isolation, empty-cache byte-identity,
an entry swept for a kernel that is gone, and the multi-block flash
kernels against the fp64 oracle across causal × bias × segment ids."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import tune
from apex_tpu.ops.flash_attention import (
    attention_reference,
    flash_attention,
)


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv(tune.ENV_CACHE_PATH, str(path))
    tune.invalidate()
    tune.reset_stats()
    yield path
    tune.invalidate()


# ------------------------------- cache layer --------------------------------

def test_cache_roundtrip(tmp_cache):
    attrs = dict(b=2, h=4, sq=64, sk=64, d=16, dtype="float32",
                 causal=True, bias="none", seg=False)
    cfg = {"block_q": 32, "block_k": 32, "fused_bwd": True}
    tune.record("flash_sdpa", attrs, cfg, meta={"ms": 1.0})
    # reload from disk (invalidate drops the memo)
    tune.invalidate()
    got = tune.tuned("flash_sdpa", attrs)
    assert got == cfg
    # the file itself is schema-stamped
    raw = json.loads(tmp_cache.read_text())
    assert raw["schema"] == tune.SCHEMA_VERSION
    assert tune.device_kind() in raw["entries"]


def test_missing_cache_is_deterministic_miss(tmp_cache):
    tune.reset_stats()
    assert tune.tuned("flash_sdpa", dict(b=1)) is None
    assert tune.tuned("flash_sdpa", dict(b=1)) is None
    s = tune.stats()
    assert s["hits"] == 0 and s["misses"] == 2


def test_corrupt_cache_falls_back(tmp_cache):
    tmp_cache.write_text("{ not json !!!")
    tune.invalidate()
    with pytest.warns(UserWarning, match="corrupt"):
        assert tune.tuned("flash_sdpa", dict(b=1)) is None
    # and a wrong-schema file is likewise ignored
    tmp_cache.write_text(json.dumps({"schema": 999, "entries": {}}))
    tune.invalidate()
    assert tune.tuned("flash_sdpa", dict(b=1)) is None


def test_device_kind_mismatch_ignored(tmp_cache):
    attrs = dict(rows=1024, hidden=128)
    tune.record("softmax_fwd", attrs, {"block_rows": 64}, kind="v5e")
    tune.invalidate()
    # current kind is "cpu" on the test host — the v5e entry must not
    # leak across device kinds
    assert tune.device_kind() != "v5e"
    assert tune.tuned("softmax_fwd", attrs) is None
    tune.record("softmax_fwd", attrs, {"block_rows": 64})
    tune.invalidate()
    assert tune.tuned("softmax_fwd", attrs) == {"block_rows": 64}


def test_disable_env(tmp_cache, monkeypatch):
    attrs = dict(rows=8, hidden=8)
    tune.record("softmax_fwd", attrs, {"block_rows": 8})
    monkeypatch.setenv(tune.ENV_DISABLE, "0")
    assert tune.tuned("softmax_fwd", attrs) is None
    monkeypatch.delenv(tune.ENV_DISABLE)
    assert tune.tuned("softmax_fwd", attrs) == {"block_rows": 8}


def test_fingerprint_tracks_content(tmp_cache):
    fp0 = tune.fingerprint()
    tune.record("opt_flat", dict(kernel="adam", rows=1024),
                {"block_rows": 256})
    fp1 = tune.fingerprint()
    assert fp0 != fp1
    assert tune.stats()["fingerprint"] == fp1


# ----------------------- empty-cache byte-identity --------------------------

def _qkv(b, h, s, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, s, d), dtype) for k in ks)


def test_empty_cache_matches_explicit_heuristics(tmp_cache):
    """With no cache entry, the tuner-consulting default path must be
    byte-identical to the pre-tuner heuristics."""
    q, k, v = _qkv(1, 2, 64, 16)
    auto = flash_attention(q, k, v, causal=True, use_pallas_override=True)
    explicit = flash_attention(q, k, v, causal=True,
                               use_pallas_override=True,
                               block_q=64, block_k=64)
    assert np.array_equal(np.asarray(auto), np.asarray(explicit))


def _traced_blocks(fn, q, k, v):
    """The (bq, bk) of the forward kernel `fn` traces, read off its
    grid, (batch * heads, q blocks, k blocks)."""
    (grid,) = re.findall(r"grid=\((\d+), (\d+), (\d+)\)",
                         str(jax.make_jaxpr(fn)(q, k, v)))
    return q.shape[2] // int(grid[1]), k.shape[2] // int(grid[2])


def test_tuned_flash_entry_is_picked_up(tmp_cache):
    """A recorded entry for the current (cpu) kind drives the default
    path — observable via the hit counter and the traced kernel's
    blocks — and stays correct."""
    b, h, s, d = 1, 4, 64, 16
    q, k, v = _qkv(b, h, s, d)
    attrs = dict(b=b, h=h, sq=s, sk=s, d=d, dtype="float32",
                 causal=True, bias="none", seg=False)
    tune.record("flash_sdpa", attrs, {"block_q": 32, "block_k": 32})
    tune.reset_stats()

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               use_pallas_override=True)

    out = attn(q, k, v)
    assert tune.stats()["hits"] == 1
    assert _traced_blocks(attn, q, k, v) == (32, 32)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_an_entry_swept_for_the_head_packed_kernel_is_ignored(tmp_cache):
    """A user's cache file outlives the kernels it was swept for: a
    `flash_sdpa` entry that still carries a packing factor above 1 was
    the optimum of the head-packed kernel, which is gone, so the whole
    entry is ignored (one warning, no error) and the call takes the
    heuristics' blocks; at a factor of 1 the sweep chose these kernels
    and its blocks hold."""
    import warnings

    import apex_tpu.ops.flash_attention as fa

    fa._BLOCK_FALLBACK_WARNED.clear()
    b, h, s, d = 1, 4, 64, 16
    q, k, v = _qkv(b, h, s, d, seed=5)
    attrs = dict(b=b, h=h, sq=s, sk=s, d=d, dtype="float32",
                 causal=False, bias="none", seg=False)
    tmp_cache.write_text(json.dumps({
        "schema": tune.SCHEMA_VERSION,
        "entries": {tune.device_kind(): {
            tune.make_key("flash_sdpa", attrs): {"config": {
                "block_q": 32, "block_k": 32, "heads_per_step": 2}},
            tune.make_key("flash_sdpa", dict(attrs, causal=True)): {
                "config": {"block_q": 32, "block_k": 16,
                           "heads_per_step": 1}}}}}))
    tune.invalidate()

    def attn(q, k, v, causal=False):
        return flash_attention(q, k, v, causal=causal,
                               use_pallas_override=True)

    with pytest.warns(UserWarning, match="out-of-range tuned config") as w:
        out = attn(q, k, v)
    assert len(w) == 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # warned once: now silent
        assert _traced_blocks(attn, q, k, v) == (64, 64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_reference(q, k, v)),
                               rtol=1e-4, atol=1e-4)
    assert _traced_blocks(
        lambda *a: attn(*a, causal=True), q, k, v) == (32, 16)


# ------------------- the multi-block kernels vs the oracle -------------------

def _oracle64(q, k, v, **kw):
    """TRUE fp64 reference (the satellite's oracle) — the conftest
    disables x64 globally, so the cast must run under enable_x64 or it
    silently truncates to fp32."""
    with jax.enable_x64():
        out = attention_reference(q.astype(jnp.float64),
                                  k.astype(jnp.float64),
                                  v.astype(jnp.float64), **kw)
        return np.asarray(out, np.float64)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bias_kind", ["none", "sk", "full"])
@pytest.mark.parametrize("seg", [False, True])
def test_multi_block_matches_oracle(causal, bias_kind, seg, tmp_cache):
    """Four (32, 32) blocks a head against the fp64 oracle: the
    forward everywhere, the gradients on the simplest and the fullest
    combination (the kernels' own parity on the rest lives in
    test_flash_attention.py)."""
    b, h, s, d = 2, 4, 64, 16
    q, k, v = _qkv(b, h, s, d, seed=7)
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    bias = None
    if bias_kind == "sk":
        bias = jax.random.normal(ks[0], (b, 1, 1, s))
    elif bias_kind == "full":
        bias = jax.random.normal(ks[0], (b, h, s, s))
    seg_ids = None
    if seg:
        seg_ids = (jnp.arange(s)[None, :] < s // 2).astype(
            jnp.int32) * jnp.ones((b, 1), jnp.int32)

    kw = dict(causal=causal, bias=bias, segment_ids=seg_ids,
              use_pallas_override=True, block_q=32, block_k=32)
    got = flash_attention(q, k, v, **kw)
    want = _oracle64(q, k, v, causal=causal, bias=bias,
                     q_segment_ids=seg_ids, kv_segment_ids=seg_ids)
    assert np.abs(np.asarray(got, np.float64) - want).max() < 1e-5

    if (causal, bias_kind, seg) not in ((False, "none", False),
                                        (True, "full", True)):
        return
    grads = jax.grad(
        lambda q, k, v: jnp.sum(jnp.sin(flash_attention(q, k, v, **kw))),
        argnums=(0, 1, 2))(q, k, v)
    with jax.enable_x64():
        def loss64(q, k, v):
            out = attention_reference(q, k, v, causal=causal,
                                      bias=None if bias is None
                                      else bias.astype(jnp.float64),
                                      q_segment_ids=seg_ids,
                                      kv_segment_ids=seg_ids)
            return jnp.sum(jnp.sin(out))

        g_or = jax.grad(loss64, argnums=(0, 1, 2))(
            q.astype(jnp.float64), k.astype(jnp.float64),
            v.astype(jnp.float64))
        g_or = [np.asarray(g, np.float64) for g in g_or]
    for a, e, name in zip(grads, g_or, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a, np.float64), np.asarray(e),
            rtol=1e-4, atol=1e-4, err_msg=f"d{name} vs oracle")


def test_multi_block_bf16_vs_oracle(tmp_cache):
    """The bf16 kernel at (64, 64) blocks ≤ 1e-2 max-abs vs the fp64
    oracle."""
    q, k, v = _qkv(1, 4, 128, 32, dtype=jnp.bfloat16, seed=9)
    got = flash_attention(q, k, v, causal=True, use_pallas_override=True,
                          block_q=64, block_k=64)
    want = _oracle64(q, k, v, causal=True)
    assert np.abs(np.asarray(got, np.float64) - want).max() < 1e-2


def test_block_fallback_warns_once_and_matches(tmp_cache):
    """Non-dividing tuned/explicit blocks degrade to the largest
    dividing block with a single warning (ISSUE 3 satellite)."""
    import apex_tpu.ops.flash_attention as fa

    fa._BLOCK_FALLBACK_WARNED.clear()
    q, k, v = _qkv(1, 2, 96, 16, seed=19)
    with pytest.warns(UserWarning, match="does not divide"):
        out = flash_attention(q, k, v, causal=True,
                              use_pallas_override=True,
                              block_q=64, block_k=64)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # warned once: a second identical call stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        flash_attention(q, k, v, causal=True, use_pallas_override=True,
                        block_q=64, block_k=64)


# ------------------------- row-block / optimizer hooks ----------------------

def test_tuned_row_block_softmax(tmp_cache):
    from apex_tpu.ops.softmax import (
        scaled_softmax,
        scaled_softmax_reference,
    )

    x = jax.random.normal(jax.random.PRNGKey(0), (96, 128))
    base = scaled_softmax(x, 2.0, use_pallas_override=True)
    tune.record("softmax_fwd",
                dict(rows=tune.pow2_bucket(96), hidden=128),
                {"block_rows": 16})
    tuned_out = scaled_softmax(x, 2.0, use_pallas_override=True)
    np.testing.assert_allclose(np.asarray(tuned_out), np.asarray(base),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(tuned_out), np.asarray(scaled_softmax_reference(x, 2.0)),
        rtol=1e-5, atol=1e-5)
    # an insane tuned value is rejected → heuristic
    tune.record("softmax_fwd",
                dict(rows=tune.pow2_bucket(96), hidden=128),
                {"block_rows": 7})
    out2 = scaled_softmax(x, 2.0, use_pallas_override=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(base),
                               rtol=1e-6, atol=1e-6)


def test_tuned_row_block_layer_norm(tmp_cache):
    from apex_tpu.ops.layer_norm import (
        fused_layer_norm,
        layer_norm_reference,
    )

    x = jax.random.normal(jax.random.PRNGKey(1), (80, 64))
    w = jnp.ones((64,)) * 1.5
    b = jnp.zeros((64,)) + 0.1
    tune.record("layer_norm_fwd",
                dict(rows=tune.pow2_bucket(80), hidden=64),
                {"block_rows": 8})
    tune.record("layer_norm_bwd",
                dict(rows=tune.pow2_bucket(80), hidden=64),
                {"block_rows": 8})

    def f(x, w, b):
        return jnp.sum(fused_layer_norm(x, w, b,
                                        use_pallas_override=True) ** 2)

    g = jax.grad(f, argnums=(0, 1, 2))(x, w, b)

    def fr(x, w, b):
        return jnp.sum(layer_norm_reference(x, w, b) ** 2)

    gr = jax.grad(fr, argnums=(0, 1, 2))(x, w, b)
    for a, e in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-4, atol=1e-4)


def test_tuned_opt_block_rows(tmp_cache):
    from apex_tpu.ops import optimizer_kernels as K

    n = K.FLAT_TILE
    rows = n // K._LANES
    p = jnp.ones((n,), jnp.float32)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    g = jnp.full((n,), 1e-2, jnp.float32)
    base = K.adam_flat(p, m, v, g, lr=1e-3, step=1,
                       use_pallas_override=True)
    tune.record("opt_flat", dict(kernel="adam",
                                 rows=tune.pow2_bucket(rows)),
                {"block_rows": 128})
    tuned_out = K.adam_flat(p, m, v, g, lr=1e-3, step=1,
                            use_pallas_override=True)
    for a, e in zip(tuned_out, base):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-6, atol=1e-6)
    # non-dividing tuned value → heuristic (512), still exact
    tune.record("opt_flat", dict(kernel="adam",
                                 rows=tune.pow2_bucket(rows)),
                {"block_rows": 384})
    out2 = K.adam_flat(p, m, v, g, lr=1e-3, step=1,
                       use_pallas_override=True)
    for a, e in zip(out2, base):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-6, atol=1e-6)


def test_check_key_roundtrip_covers_all_committed_defaults():
    """tune --check derives sweep shapes from the committed keys
    themselves — every v5e default must round-trip through the parser
    to a sweepable (op, attrs)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    from scripts.gpt_anatomy import _parse_key_attrs

    from apex_tpu.tune import defaults

    for kind, entries in defaults.DEFAULTS.items():
        for key in entries:
            op, attrs = _parse_key_attrs(key)
            if op == "flash_sdpa":
                # re-keying the parsed attrs must reproduce the key
                assert tune.make_key(op, attrs) == key
                assert attrs["sq"] == attrs["sk"]  # sweepable shape
                assert attrs["bias"] == "none"
            else:
                # `delta_rule` has no sweep driver: --check names it and
                # goes on
                assert op in ("opt_flat", "delta_rule")
                assert tune.make_key(op, attrs) == key


# ------------------------------- search driver ------------------------------

@pytest.mark.slow
@pytest.mark.l1
def test_search_sweep_records_winner(tmp_cache):
    """Full (tiny-shape, interpret-mode) sweep: the driver times every
    candidate, records the winner, and the kernels then hit it."""
    from apex_tpu.tune import search

    best, results = search.tune_flash(
        1, 2, 128, 16, dtype=jnp.float32, causal=True, iters=1,
        use_pallas_override=True)
    assert results and best in [c for c, _ in results]
    tune.invalidate()
    attrs = search.flash_attrs(1, 2, 128, 16, jnp.float32, True)
    assert tune.tuned("flash_sdpa", attrs) == best


@pytest.mark.slow
@pytest.mark.l1
def test_search_opt_flat_sweep(tmp_cache):
    from apex_tpu.tune import search

    best, results = search.tune_opt_flat(2 * 512 * 128, iters=1,
                                         use_pallas_override=True)
    assert best["block_rows"] in (128, 256, 512)
