"""Solar-Open2's forward pass and token cross entropies, plainly, for
the share of the model one chip holds.

Written from the keys of the model's public `config.json`
(`model_type` `solar_open2`) and from the papers its keys and its
description name: Kimi Delta Attention for the `kda_*` keys and
`linear_attn_config` (Kimi Linear, arXiv:2510.26692), gated attention
for `use_gqa_gate` (arXiv:2505.06708), and the DeepSeek-V3 family's
expert layer for `n_routed_experts`, `n_shared_experts`,
`norm_topk_prob`, `routed_scaling_factor` (arXiv:2412.19437, 2.1.2).

**The equations.**  Pre-norm blocks, RMSNorm (`rms_norm_eps`) without
bias: `h = x + Mixer_i(RMSNorm(x))`, `y = h + MoE(RMSNorm(h))`.
`Mixer_i` is gated attention where `i` is in `gqa_layers`, KDA
otherwise.  `use_rope` is false: no rotary embedding and no position
table anywhere.

* KDA, n = `linear_attn_config.num_heads` heads of d_k = d_v =
  `linear_attn_config.head_dim`:
  `q~, k~, v~ = a W_q, a W_k, a W_v` (H -> n d each);
  `q, k, v = SiLU(conv(.))`, a causal depthwise convolution over time
  of `short_conv_kernel_size` taps, a weight a channel and tap, no
  bias (`conv[j]` weighs the token `taps - 1 - j` back);
  a head's `q <- q / |q|_2 * d_k^-1/2`, `k <- k / |k|_2`;
  the log-decay a channel `g_t = -exp(A_h) * softplus(W_f2 (W_f1 a_t) +
  b_dt)`, `W_f1`: H -> d, `W_f2`: d -> n d_k, `A_h` a scalar a head,
  `b_dt` a bias a channel; `alpha_t = exp(g_t)` in (0, 1);
  `beta_t = 2 sigmoid(W_beta a_t)` in (0, 2) a head
  (`kda_allow_neg_eigval`: the 2 lets `I - beta k k^T` reach -1);
  a head's state, `S_0 = 0`, `S_t` of (d_k, d_v):
  **`S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t
  v_t^T`**, `o_t = S_t^T q_t`, computed here by that recurrence, a
  token at a time (`lax.scan` over t), never by a chunked algebra;
  `out = W_o [RMSNorm_dv(o_t) * sigmoid(W_g2 (W_g1 a_t))]`, the norm's
  weight of d_v shared by the heads, `W_g1`: H -> d, `W_g2`: d -> n d_v.
* gated grouped-query attention: `q = a W_q` (heads x d), `k, v =
  a W_k, a W_v` (kv heads x d), causal `softmax(q k^T / sqrt(d)) v`,
  kv head j repeated for query heads `8 j ... 8 j + 7`;
  `out = W_o [attn * sigmoid(a W_gate)]`, `W_gate`: H -> heads x d.
* the expert layer: `s = sigmoid(m W_r)` over all the published
  experts; the `num_experts_per_tok` experts with the largest `s + b`
  are chosen (`b` for the choice only); weights `routed_scaling_factor
  * s_e / (sum of the chosen s + 1e-20)`; `y = sum_chosen w_e
  SwiGLU_e(m) + SwiGLU_shared(m)`, no token dropped.
* loss: cross entropy over the held rows of the untied head.

**The share.**  `arch` (the benchmark's configuration file) says what
is held: `num_hidden_layers` layers, experts `[experts_first,
experts_first + n_routed_experts)` of the `n_routed_experts_published`
the router scores, `vocab_size` rows of embedding and head.  What the
experts held elsewhere would add to a token is left out, and that
partial result goes on to the next layer.  Given every expert this is
the uncut layer.

Everything is `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernel, no sharding, no
import from `apex_tpu`.  Attention runs a block of queries at a time,
the head a block of tokens at a time, so that 4096 tokens at the
published widths fit beside the system under test.  Weights are handed
over as the program lays them out (tensor parallelism 1):

    embed.weight, head.weight (V, H)   final_ln.weight (H,)
    block<i>.ln1.weight, .ln2.weight (H,)
    block<i>.attn, attention: q, gate (H, heads d), k, v (H, kv d),
        proj (heads d, H); a head's columns lie together
    block<i>.attn, KDA: q, k, v (H, n d), conv_q, conv_k, conv_v
        (taps, n d), f_a (H, d), f_b (d, n d), a_log (n,), dt_bias
        (n d,), beta (H, n), g_a (H, d), g_b (d, n d), o_norm.weight
        (d,), proj (n d, H)
    block<i>.mlp: router (H, E), router_bias (E,), experts_gate_up
        (held, H, 2f), experts_down (held, f, H), shared_gate_up
        (H, 2f), shared_down (f, H)

What the config does not carry is assumed, and the configuration file
lists each under `assumed`: the two rank-d pairs for
`kda_use_full_proj` false; the gate's form; no QK norm and no bias in
the attention; sigmoid scores with a selection-only bias in the
router; how `A_h`, `b_dt` and the convolution start.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

Q_BLOCK = 512        # queries a block of attention scores
HEAD_BLOCK = 1024    # tokens a block of logits


def _f32(tree, device):
    return jax.tree.map(
        lambda a: jax.device_put(a, device).astype(jnp.float32), tree)


class _Arch(NamedTuple):
    """The sizes the forward needs (hashable: a static argument)."""

    heads: int
    kv_heads: int
    head_dim: int
    kda_heads: int
    kda_dim: int
    neg_eigval: bool
    attends: tuple
    eps: float
    layers: int
    top_k: int
    first: int
    held: int
    scale: float
    renormalize: bool

    @classmethod
    def of(cls, arch):
        """From the configuration's keys; an _Arch as it is."""
        if isinstance(arch, cls):
            return arch
        linear = arch["linear_attn_config"]
        return cls(
            heads=int(arch["num_attention_heads"]),
            kv_heads=int(arch["num_key_value_heads"]),
            head_dim=int(arch["head_dim"]),
            kda_heads=int(linear["num_heads"]),
            kda_dim=int(linear["head_dim"]),
            neg_eigval=bool(arch["kda_allow_neg_eigval"]),
            attends=tuple(int(i) for i in arch["gqa_layers"]),
            eps=float(arch["rms_norm_eps"]),
            layers=int(arch["num_hidden_layers"]),
            top_k=int(arch["num_experts_per_tok"]),
            first=int(arch.get("experts_first", 0)),
            held=int(arch["n_routed_experts"]),
            scale=float(arch["routed_scaling_factor"]),
            renormalize=bool(arch["norm_topk_prob"]))


def _rounded(x, dtype):
    """x rounded to `dtype`'s exponent and mantissa, still float32; x
    where None.  `reduce_precision` and not a pair of casts: the TPU
    compiler may drop a cast to bf16 and back as excess precision."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _mm(a, b, dtype):
    """a @ b, both operands rounded to `dtype` first, the product
    float32."""
    return _rounded(a, dtype) @ _rounded(b, dtype)


def _rms_norm(x, p, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * p["weight"]


def _swiglu(x, w_gate_up, w_down, dtype):
    gate, up = jnp.split(_mm(x, w_gate_up, dtype), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, w_down, dtype)


def _attention(p, a, arch, dtype):
    b, s, _ = a.shape
    nh, nkv, d = arch.heads, arch.kv_heads, arch.head_dim
    q = _mm(a, p["q"], dtype).reshape(b, s, nh, d)
    # a kv head a query head: head j of k and v, 8 times over
    k, v = (jnp.repeat(_mm(a, p[x], dtype).reshape(b, s, nkv, d),
                       nh // nkv, axis=2) for x in "kv")
    blocks = []
    for start in range(0, s, Q_BLOCK):       # a block of queries at a time
        stop = min(start + Q_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk",
                            _rounded(q[:, start:stop], dtype),
                            _rounded(k[:, :stop], dtype)) / math.sqrt(d)
        causal = (jnp.arange(start, stop)[:, None]
                  >= jnp.arange(stop)[None, :])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("bhqk,bkhd->bqhd", _rounded(probs, dtype),
                                 _rounded(v[:, :stop], dtype)))
    ctx = jnp.concatenate(blocks, axis=1).reshape(b, s, nh * d)
    return _mm(ctx * jax.nn.sigmoid(_mm(a, p["gate"], dtype)), p["proj"],
               dtype)


def _conv_silu(x, w):
    """x (B, S, C), w (taps, C): y_t = sum_j w_j x_(t - taps + 1 + j)."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + s] * w[j] for j in range(taps)))


def delta_rule(q, k, v, g, beta, state_dtype=None):
    """The recurrence of a head, a token at a time.  q, k, g (B, S, n,
    d_k), v (B, S, n, d_v), beta (B, S, n) -> o (B, S, n, d_v).
    `state_dtype` rounds the state after every token: what a program
    that carried it in that dtype would compute."""

    def token(state, x):
        qt, kt, vt, gt, bt = x                   # (B, n, d) ... (B, n)
        state = state * jnp.exp(gt)[..., None]
        seen = jnp.einsum("bnkv,bnk->bnv", state, kt)
        state = state + jnp.einsum("bnk,bnv->bnkv", kt,
                                   bt[..., None] * (vt - seen))
        state = _rounded(state, state_dtype)
        return state, jnp.einsum("bnkv,bnk->bnv", state, qt)

    b, _, n, dk = q.shape
    first = jnp.zeros((b, n, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(token, first, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def scan_outputs(q, k, v, g, beta, *, device=None, state_dtype=None):
    """`delta_rule` over head-major arrays, as a program's chunked op
    takes them: q, k, g (B, n, S, d_k), v (B, n, S, d_v), beta (B, n,
    S), of any float dtype -> o (B, n, S, d_v) float32, computed in
    float32 from the values handed over.  The benchmark holds the
    program's op to it at the cell's own shape."""
    device = device or jax.devices()[0]
    args = [jnp.moveaxis(jax.device_put(x, device).astype(jnp.float32), 1, 2)
            for x in (q, k, v, g, beta)]
    with jax.default_matmul_precision("highest"):
        o = jax.jit(delta_rule, static_argnames="state_dtype")(
            *args, state_dtype=state_dtype)
    return jnp.moveaxis(o, 2, 1)


def _kda(p, a, arch, dtype, state_dtype):
    b, s, _ = a.shape
    n, d = arch.kda_heads, arch.kda_dim
    q, k, v = (_conv_silu(_mm(a, p[x], dtype), p["conv_" + x]
                          ).reshape(b, s, n, d) for x in "qkv")
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    f = _mm(_mm(a, p["f_a"], dtype), p["f_b"], dtype) + p["dt_bias"]
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(f).reshape(b, s, n, d)
    beta = jax.nn.sigmoid(_mm(a, p["beta"], dtype))
    if arch.neg_eigval:
        beta = 2.0 * beta
    o = delta_rule(q, k, v, g, beta, state_dtype)
    o = _rms_norm(o, p["o_norm"], arch.eps).reshape(b, s, n * d)
    gate = _mm(_mm(a, p["g_a"], dtype), p["g_b"], dtype)
    return _mm(o * jax.nn.sigmoid(gate), p["proj"], dtype)


def _experts(p, m, arch, dtype):
    """The held experts' part of the routed sum, plus the shared
    expert.  m: (T, H)."""
    scores = jax.nn.sigmoid(_mm(m, p["router"], dtype))          # (T, E)
    biased = scores + p["router_bias"]
    # the top_k largest, ties to the lower index
    chosen = jnp.argsort(-biased, axis=-1, stable=True)[:, :arch.top_k]
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if arch.renormalize:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * arch.scale
    y = _swiglu(m, p["shared_gate_up"], p["shared_down"], dtype)
    for e in range(arch.held):
        # this expert's weight for every token: 0 where it was not chosen
        g = jnp.sum(jnp.where(chosen == arch.first + e, weight, 0.0), axis=-1)
        y = y + g[:, None] * _swiglu(m, p["experts_gate_up"][e],
                                     p["experts_down"][e], dtype)
    return y


@functools.partial(jax.jit,
                   static_argnames=("arch", "attends", "dtype", "state_dtype"))
def _block(p, x, *, arch, attends, dtype, state_dtype):
    a = _rms_norm(x, p["ln1"], arch.eps)
    x = x + (_attention(p["attn"], a, arch, dtype) if attends
             else _kda(p["attn"], a, arch, dtype, state_dtype))
    m = _rms_norm(x, p["ln2"], arch.eps)
    b, s, h = m.shape
    return x + _experts(p["mlp"], m.reshape(b * s, h), arch,
                        dtype).reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head_losses(head, norm, x, labels, *, eps, dtype):
    def one(args):                          # a block of tokens at a time
        xs, ls = args
        logits = _mm(_rms_norm(xs, norm, eps), head.T, dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, ls[:, None], axis=-1)[:, 0]
    b, s, h = x.shape
    rows = math.gcd(s, HEAD_BLOCK)
    losses = jax.lax.map(one, (x.reshape(-1, rows, h),
                               labels.reshape(-1, rows)))
    return losses.reshape(b, s)


def token_losses(params, tokens, labels, *, arch, device=None,
                 matmul_dtype=None, state_dtype=None):
    """(main, None): (B, S) float32 cross entropies of every token of
    `tokens` (B, S) against `labels` under the network `params` and the
    share `arch` describes; None for the second head the MoE job's
    other model has.

    `matmul_dtype` rounds both operands of every matrix product to that
    dtype first, `state_dtype` the KDA state after every token: what
    the same network computes at that precision.  The benchmark reads
    its tolerances against them."""
    a = _Arch.of(arch)
    device = device or jax.devices()[0]
    tokens = jax.device_put(tokens, device)
    labels = jax.device_put(labels, device)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"]["weight"], device)[tokens]
        for i in range(a.layers):
            h = _block(_f32(params[f"block{i}"], device), h, arch=a,
                       attends=i in a.attends, dtype=matmul_dtype,
                       state_dtype=state_dtype)
        main = _head_losses(_f32(params["head"]["weight"], device),
                            _f32(params["final_ln"], device), h, labels,
                            eps=a.eps, dtype=matmul_dtype)
    return main, None


def loss(params, tokens, labels, *, arch, device=None):
    """The mean over tokens: what a training step minimises.
    `jax.grad` of it gives the reference's gradient of every leaf (the
    router bias gets none: it only steers the choice)."""
    return jnp.mean(token_losses(params, tokens, labels, arch=arch,
                                 device=device)[0])


def expert_layer(p, m, *, arch):
    """One expert layer alone, for the share test: (T, H) -> (T, H),
    the held experts' part of the routed sum plus the shared expert."""
    a = _Arch.of(arch)
    with jax.default_matmul_precision("highest"):
        return _experts(jax.tree.map(lambda w: w.astype(jnp.float32), p),
                        m.astype(jnp.float32), a, None)
