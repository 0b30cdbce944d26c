"""`ops.conv_stage.stage_conv_heads`: the Pallas pair in interpret mode
against the `jax.numpy` body, outputs and the gradients of the streams
and of the taps, at blocks small enough that a row has several; which
path a call takes, and that it is counted."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import conv_stage as CS

D = 128
F32, BF16 = jnp.float32, jnp.bfloat16
Q, K, V = D ** -0.5, 1.0, None      # the scales of KDA's three streams

# name -> (dtype, heads, tokens a block, the tokens that start a
# document (None: no ids), the streams' scales[, a head's width in each
# stream, D where not given]).  A row is three blocks; a block of the
# kernels is as long as the dtype's halo allows.
CASES = {
    "f32-qkv": (F32, 2, 16, None, (Q, K, V)),
    "f32-qkv-docs": (F32, 2, 16, (5, 16, 33, 34, 35), (Q, K, V)),
    # a document that starts on a block's first, second and third row:
    # the taps of its first tokens would reach across the block's edge
    "f32-doc-on-a-blocks-first-row": (F32, 2, 16, (16,), (Q,)),
    "f32-doc-on-a-blocks-second-row": (F32, 2, 16, (17,), (K,)),
    "f32-doc-on-a-blocks-third-row": (F32, 2, 16, (34,), (V,)),
    # one document a token over the row's first three and a block's last
    "f32-docs-of-one-token": (F32, 2, 16, (1, 2, 3, 31, 32, 47), (Q, V)),
    "f32-4x128-lanes": (F32, 4, 16, (20,), (K, V)),
    "f32-blocks-of-64": (F32, 2, 64, (64, 65, 130), (Q, K, V)),
    "bf16-qkv": (BF16, 2, 32, None, (Q, K, V)),
    "bf16-qkv-docs": (BF16, 4, 32, (31, 32, 65, 66), (Q, K, V)),
    "bf16-unscaled": (BF16, 2, 32, (33,), (V,)),
    # Gated DeltaNet's two widths in one pass: 2 heads of 96 span no
    # whole lane tiles but both together, which a block then holds
    "f32-96-96-192-docs": (F32, 2, 16, (5, 16, 33, 34), (96 ** -0.5, K, V),
                           (96, 96, 192)),
}
TOL = {F32: 1e-5, BF16: 2.0 ** -6}     # of an output's largest entry


def _inputs(dtype, heads, rows, starts, scales, widths=None, batch=2,
            seed=0):
    s, n = 3 * rows, len(scales)
    widths = widths or (D,) * n
    ks = jax.random.split(jax.random.PRNGKey(seed), 3 * n)
    xs = tuple(jax.random.normal(k, (batch, s, heads * d)).astype(dtype)
               for k, d in zip(ks[:n], widths))
    ws = tuple((jax.random.uniform(k, (4, heads * d), minval=-1.0) / 2
                ).astype(dtype) for k, d in zip(ks[n:2 * n], widths))
    cots = tuple(jax.random.normal(k, (batch, heads, s, d)).astype(dtype)
                 for k, d in zip(ks[2 * n:], widths))
    ids = None
    if starts is not None:
        first = jnp.zeros((batch, s), jnp.int32).at[:, list(starts)].set(1)
        # the second row's documents start a token later
        first = first.at[1].set(jnp.roll(first[1], 1))
        ids = jnp.cumsum(first, axis=1, dtype=jnp.int32)
    return xs, ws, cots, ids


@functools.lru_cache(maxsize=None)
def _both(case):
    """{kernels: (outs, dxs, dws)} of a case, by the Pallas pair
    (interpret mode) and by `jax.numpy`."""
    dtype, heads, rows, starts, scales, *widths = CASES[case]
    xs, ws, cots, ids = _inputs(dtype, heads, rows, starts, scales,
                                *widths)
    was = CS.ROWS
    CS.ROWS = rows
    try:
        def run(kernels):
            outs, pull = jax.vjp(lambda xs, ws: CS.stage_conv_heads(
                xs, ws, heads, scales, ids=ids,
                use_pallas_override=kernels), xs, ws)
            return (outs, *pull(cots))
        return {kernels: run(kernels) for kernels in (True, False)}
    finally:
        CS.ROWS = was


@pytest.mark.parametrize("what", range(3), ids=["out", "dx", "dw"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_match_the_jnp_body(case, what):
    dtype = CASES[case][0]
    pair = _both(case)
    for got, want in zip(pair[True][what], pair[False][what], strict=True):
        assert got.shape == want.shape and got.dtype == want.dtype
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=0,
            atol=TOL[dtype] * np.abs(want).max())


def test_a_rows_first_tokens_read_nothing_before_the_row():
    """Tokens 0, 1, 2 with fewer taps than the rest, exactly: the
    kernels' first block counts the rows before it as zeros though the
    block spec hands it the row's own first."""
    (x,), (w,), _, _ = _inputs(F32, 2, 16, None, (V,), batch=1)
    (got,) = CS.stage_conv_heads((x,), (w,), 2, (V,),
                                 use_pallas_override=True)
    pre = [sum(x[0, t - r] * w[3 - r] for r in range(t + 1))
           for t in range(3)]
    want = jax.nn.silu(jnp.stack(pre)).reshape(3, 2, D).transpose(1, 0, 2)
    np.testing.assert_allclose(got[0, :, :3], want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("why,shape,heads,dtype", [
    ("a 64-wide head", (1, 32, 4 * 64), 4, F32),
    ("a row off the sublane tile", (1, 40, 2 * D), 2, BF16),
    ("a row off the float32 tile", (1, 12, 2 * D), 2, F32),
])
def test_other_shapes_take_the_jnp_body_and_are_counted(why, shape, heads,
                                                        dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    w = jnp.full((4, shape[-1]), 0.25, dtype)
    CS.reset_stats()
    call = lambda x, w: CS.stage_conv_heads(
        (x,), (w,), heads, (K,), use_pallas_override=True)
    text = str(jax.make_jaxpr(call)(x, w))
    assert "conv_stage" not in text, why
    assert CS.stats() == {"calls": 1, "kernel_calls": 0}
    (got,) = call(x, w)
    (want,) = CS.stage_conv_heads_reference((x,), (w,), heads, (K,))
    np.testing.assert_array_equal(got, want)
    CS.reset_stats()


@pytest.mark.parametrize("kernels", [True, False, None],
                         ids=["asked", "refused", "cpu"])
def test_a_call_is_counted_by_the_path_it_takes(kernels):
    """Lane-wide heads over a row of whole tiles: the pair where it is
    asked for (here, or by the chip), named for the trace's readers in
    both directions; off the chip nobody asks."""
    x = jnp.ones((1, 32, 2 * D), BF16)
    w = jnp.ones((4, 2 * D), BF16)
    ids = jnp.zeros((1, 32), jnp.int32)

    def loss(x, w):
        return sum(o.astype(F32).sum() for o in CS.stage_conv_heads(
            (x, x), (w, w), 2, (Q, V), ids=ids,
            use_pallas_override=kernels))

    CS.reset_stats()
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, w))
    taken = bool(kernels)
    assert CS.stats() == {"calls": 1, "kernel_calls": int(taken)}
    # one call a direction for the two streams
    assert text.count("name=conv_stage") == taken
    assert text.count("name=conv_unstage") == taken
    CS.reset_stats()


def test_the_backward_keeps_the_streams_the_taps_and_the_ids():
    """Nothing float32 and nothing head-major waits for the backward."""
    x = jnp.ones((1, 32, 2 * D), BF16)
    w = jnp.ones((4, 2 * D), BF16)
    ids = jnp.zeros((1, 32), jnp.int32)
    _, pull = jax.vjp(lambda x, w: CS.stage_conv_heads(
        (x,), (w,), 2, (Q,), ids=ids, use_pallas_override=True), x, w)
    kept = sorted((r.shape, r.dtype.name) for r in jax.tree.leaves(pull)
                  if hasattr(r, "shape") and r.size > 1)
    assert kept == sorted([((1, 32, 2 * D), "bfloat16"),
                           ((4, 2 * D), "bfloat16"), ((1, 32), "int32")])


@pytest.mark.parametrize("bad", ["a stream's shape", "the taps", "the ids",
                                 "the scales"])
def test_operands_that_do_not_belong_together_are_refused(bad):
    x, w = jnp.ones((1, 32, 2 * D)), jnp.ones((4, 2 * D))
    xs, ws, scales, ids = [x, x], [w, w], [Q, K], jnp.zeros((1, 32),
                                                           jnp.int32)
    if bad == "a stream's shape":
        xs[1] = x[:, :16]
    elif bad == "the taps":
        ws[1] = w[:, :D]
    elif bad == "the ids":
        ids = ids[:, :16]
    else:
        scales = [Q]
    with pytest.raises(ValueError, match="streams"):
        CS.stage_conv_heads(xs, ws, 2, scales, ids=ids)


@pytest.mark.parametrize("packed", [False, True], ids=["rows", "documents"])
def test_the_models_scan_inputs_are_the_same_by_either_path(packed):
    """`HybridMoE.scan_inputs` at lane-wide KDA heads: the kernels, as
    `flash_override` asks for them, read the documents' ids and give
    what the `jax.numpy` body gives from the taps' masks."""
    from apex_tpu.models.hybrid_moe import HybridMoE, HybridMoEConfig

    def inputs(override):
        model = HybridMoE(HybridMoEConfig(
            vocab_size=64, hidden=32, num_layers=2, attention_layers=(0,),
            num_heads=2, num_kv_heads=1, head_dim=8, kda_heads=2,
            kda_head_dim=D, kda_rank=8, moe_intermediate_size=8,
            n_routed_experts=8, num_experts_per_tok=2, experts_count=4,
            eod_token_id=63 if packed else None, flash_override=override))
        p = model.init(jax.random.PRNGKey(0))["block1"]["attn"]
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 56, 64)
        a = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 32))
        return model.scan_inputs(p, a, model.documents(tokens))

    CS.reset_stats()
    got, want = inputs(True), inputs(None)
    assert CS.stats() == {"calls": 2, "kernel_calls": 1}
    CS.reset_stats()
    for g, w in zip(got, want, strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_a_stages_blocks_span_whole_lane_tiles_of_every_stream():
    """Heads a block: the most of (4, 2, 1) that fill whole lane tiles
    in every stream, else all the row's heads, a block as wide as the
    array; rows as many as keep the widest float32 block in
    BLOCK_BYTES.  KDA's cells keep (256, 4)."""
    assert CS._blocks(4096, 64, (128,) * 3, 4, BF16) == (256, 4)
    assert CS._blocks(8192, 32, (128,) * 3, 4, BF16) == (256, 4)
    assert CS._blocks(8192, 30, (96, 96, 192), 4, BF16) == (128, 30)
    assert CS._blocks(8192, 8, (96, 96), 4, BF16) == (256, 4)
    assert CS._blocks(8192, 6, (96, 192), 4, BF16) == (256, 6)
    assert CS._blocks(8192, 8, (64, 128), 4, BF16) is None
    assert CS._blocks(40, 2, (D,), 4, BF16) is None
