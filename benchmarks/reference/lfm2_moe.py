"""LFM2-MoE's forward pass and token cross entropies, plainly, for the
share of the model one chip holds.

Written from the keys of the model's public `config.json`
(`model_type` `lfm2_moe`: `layer_types`, `conv_L_cache`, `conv_bias`,
`num_key_value_heads`, `rope_theta`, `num_dense_layers`, `num_experts`,
`num_experts_per_tok`, `use_expert_bias`, `norm_topk_prob`,
`routed_scaling_factor`, `norm_eps`) and, for what the keys do not say
(the order of the input projection's thirds, the taps' order, the norm
of q and k before the rotation, the rotate-half convention), from the
model's published modelling code as remembered: no network here to read
it again; the configuration file lists each under `assumed`.

**The equations.**  Pre-norm blocks, RMSNorm (`norm_eps`), no bias
anywhere: `h = x + Mixer_i(RMSNorm(x))`, `y = h + FFN_i(RMSNorm(h))`,
`a` a block's normed input; a final RMSNorm before the head.

* gated short convolution (`layer_types[i]` = `conv`): `[B | C | u] =
  a W_in` (H -> 3H, three H-wide thirds in this order); `z = B * u`;
  `c_t = sum_(j=0..L-1) w[:, j] * z_(t-L+1+j)` with `z` = 0 before the
  row's first token, L = `conv_L_cache`: a causal depthwise convolution,
  one weight a channel and tap, `w[:, L-1]` on the current token
  (PyTorch's `Conv1d(H, H, L, groups=H, padding=L-1)` cut to the first
  S outputs); `out = (C * c) W_out`.  No activation in the mixer.
* attention (`full_attention`): `q = a W_q` (heads x d), `k, v = a W_k,
  a W_v` (kv_heads x d), d = `hidden_size / num_attention_heads`; q and
  k each through an RMSNorm over a head's d channels (one weight of d
  for q, one for k, shared by the heads); rotate-half rotary embedding
  over all d channels, `x cos + rotate_half(x) sin` with `rotate_half(
  x1 | x2) = (-x2 | x1)` and the angle of channel i and i + d/2 at
  position t `t * rope_theta^(-2i/d)`; causal `softmax(q k^T / sqrt(d))
  v`, kv head j serving query heads `j * group ... (j + 1) * group - 1`;
  `out = ctx W_o`; no gate.
* dense FFN (the leading `num_dense_layers` layers): `W_2 (silu(W_1 m)
  * W_3 m)`, `intermediate_size` wide.
* expert layer (the others): `s = sigmoid(m W_r)` over all the
  published experts; the `num_experts_per_tok` largest of `s + b` are
  chosen (`use_expert_bias`: `b` for the choice only); weights the
  chosen experts' own `s` over their sum (`norm_topk_prob`; + 1e-20
  here where the source adds 1e-6: under 1e-6 relative) times
  `routed_scaling_factor`; each expert a SwiGLU of
  `moe_intermediate_size`; **no shared expert**, no token dropped.
* loss: cross entropy over the held rows of the head, every position;
  the head's weight is the embedding's where `tie_word_embeddings`.

**The share.**  `arch` (the benchmark's configuration file) says what
is held: `num_hidden_layers` layers of the kinds `layer_types` lists,
experts `[experts_first, experts_first + num_experts)` of the
`num_experts_published` the router scores, `vocab_size` rows of the
embedding.  What the experts held elsewhere would add to a token is
left out.  Given every expert this is the uncut layer.

Everything is `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernel, no sharding, no
import from `apex_tpu`; the convolution is `L` shifted products, the
attention a dense masked softmax a block of queries at a time; the
small pieces it has in common with `solar_open2.py` (the norm, the
SwiGLU, the blocked head) are that file's.  Weights are handed over as
the program lays them out:

    embed.weight (V, H)   final_ln.weight (H,)   head.weight (V, H)
        where the head is not tied
    block<i>.ln1.weight, .ln2.weight (H,)
    block<i>.attn, conv: in_proj (H, 3H), conv (H, L), out_proj (H, H)
    block<i>.attn, attention: q (H, heads d), k, v (H, kv_heads d),
        q_norm.weight, k_norm.weight (d,), proj (heads d, H); a head's
        columns lie together
    block<i>.mlp, dense: gate_up (H, 2f) = [W_1 | W_3], down (f, H)
    block<i>.mlp, experts: router (H, E), router_bias (E,),
        experts_gate_up (held, H, 2f), experts_down (held, f, H)
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.solar_open2 import (
    _f32,
    _head_losses,
    _mm,
    _rms_norm,
    _rounded,
    _swiglu,
)

Q_BLOCK = 512        # queries a block of attention scores


class _Arch(NamedTuple):
    """The sizes the forward needs (hashable: a static argument)."""

    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    kinds: tuple
    dense: int
    eps: float
    top_k: int
    first: int
    held: int
    scale: float
    renormalize: bool
    tied: bool

    @classmethod
    def of(cls, arch):
        """From the configuration's keys; an _Arch as it is."""
        if isinstance(arch, cls):
            return arch
        layers = int(arch["num_hidden_layers"])
        return cls(
            heads=int(arch["num_attention_heads"]),
            kv_heads=int(arch["num_key_value_heads"]),
            head_dim=int(arch["hidden_size"])
            // int(arch["num_attention_heads"]),
            theta=float(arch["rope_theta"]),
            kinds=tuple(arch["layer_types"][:layers]),
            dense=int(arch["num_dense_layers"]),
            eps=float(arch["norm_eps"]),
            top_k=int(arch["num_experts_per_tok"]),
            first=int(arch.get("experts_first", 0)),
            held=int(arch["num_experts"]),
            scale=float(arch["routed_scaling_factor"]),
            renormalize=bool(arch["norm_topk_prob"]),
            tied=bool(arch["tie_word_embeddings"]))


def short_conv(p, a, dtype=None, taps_reversed=False):
    """The gated short convolution of a (B, S, H): three shifted
    products between two gates.  `taps_reversed` reads the taps in the
    opposite order in time: what a convolution that took the wrong tap
    for the current token would compute."""
    s = a.shape[1]
    b, c, u = jnp.split(_mm(a, p["in_proj"], dtype), 3, axis=-1)
    z = b * u
    w = p["conv"][:, ::-1] if taps_reversed else p["conv"]
    taps = w.shape[1]
    conv = 0.0
    for j in range(taps):
        back = taps - 1 - j                  # tap j reads z_(t - back)
        conv = conv + w[:, j] * jnp.pad(
            z, ((0, 0), (back, 0), (0, 0)))[:, :s]
    return _mm(c * conv, p["out_proj"], dtype)


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def normed_rotated(x, norm, arch):
    """x (B, S, n, d): a head's channels normed, then turned by the
    token's position."""
    d = arch.head_dim
    x = _rms_norm(x, norm, arch.eps)
    inv_freq = arch.theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None]   # (S, 1, d)
    return x * jnp.cos(angle) + rotate_half(x) * jnp.sin(angle)


def _attention(p, a, arch, dtype):
    b, s, _ = a.shape
    nh, nkv, d = arch.heads, arch.kv_heads, arch.head_dim
    q = normed_rotated(_mm(a, p["q"], dtype).reshape(b, s, nh, d),
                       p["q_norm"], arch)
    k = normed_rotated(_mm(a, p["k"], dtype).reshape(b, s, nkv, d),
                       p["k_norm"], arch)
    v = _mm(a, p["v"], dtype).reshape(b, s, nkv, d)
    # a kv head a query head: head j of k and v, group times over
    k, v = (jnp.repeat(x, nh // nkv, axis=2) for x in (k, v))
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
    return _mm(ctx, p["proj"], dtype)


def _route(p, m, arch, dtype, router_dtype=None):
    """(chosen (T, top_k) expert ids of all the published, weight (T,
    top_k)).  `router_dtype` rounds the router's operands and its
    scores to that dtype."""
    scores = _rounded(jax.nn.sigmoid(_mm(m, p["router"], router_dtype
                                         or dtype)), router_dtype)
    biased = scores + p["router_bias"]
    # the top_k largest, ties to the lower index
    chosen = jnp.argsort(-biased, axis=-1, stable=True)[:, :arch.top_k]
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if arch.renormalize:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen, weight * arch.scale


def _experts(p, m, arch, dtype, router_dtype=None):
    """The held experts' part of the routed sum; nothing is shared.
    m: (T, H)."""
    chosen, weight = _route(p, m, arch, dtype, router_dtype)
    y = jnp.zeros_like(m)
    for e in range(arch.held):
        # this expert's weight for every token: 0 where it was not chosen
        g = jnp.sum(jnp.where(chosen == arch.first + e, weight, 0.0), axis=-1)
        y = y + g[:, None] * _swiglu(m, p["experts_gate_up"][e],
                                     p["experts_down"][e], dtype)
    return y


@functools.partial(jax.jit, static_argnames=(
    "arch", "kind", "dense", "dtype", "router_dtype", "taps_reversed"))
def _block(p, x, *, arch, kind, dense, dtype, router_dtype, taps_reversed):
    a = _rms_norm(x, p["ln1"], arch.eps)
    if kind == "conv":
        x = x + short_conv(p["attn"], a, dtype, taps_reversed)
    elif kind == "full_attention":
        x = x + _attention(p["attn"], a, arch, dtype)
    else:
        raise ValueError(f"layer type {kind!r}")
    m = _rms_norm(x, p["ln2"], arch.eps)
    if dense:
        return x + _swiglu(m, p["mlp"]["gate_up"], p["mlp"]["down"], dtype)
    b, s, h = m.shape
    return x + _experts(p["mlp"], m.reshape(b * s, h), arch, dtype,
                        router_dtype).reshape(b, s, h)


def _trunk(params, tokens, a, device, **kw):
    """(the residual stream after the last held layer, the head's
    weight), inside the caller's precision context."""
    embed = _f32(params["embed"]["weight"], device)
    h = embed[jax.device_put(tokens, device)]
    for i, kind in enumerate(a.kinds):
        h = _block(_f32(params[f"block{i}"], device), h, arch=a, kind=kind,
                   dense=i < a.dense, **kw)
    return h, (embed if a.tied else _f32(params["head"]["weight"], device))


def token_losses(params, tokens, labels, *, arch, device=None,
                 matmul_dtype=None, router_dtype=None, taps_reversed=False):
    """(main, None): (B, S) float32 cross entropies of every token of
    `tokens` (B, S) against `labels` under the network `params` and the
    share `arch` describes; None for the second head another job's
    model has.

    `matmul_dtype` rounds both operands of every matrix product to that
    dtype first, `router_dtype` the router's alone and its scores;
    `taps_reversed` turns every convolution's taps round in time.  The
    benchmark reads its tolerances against them."""
    a = _Arch.of(arch)
    device = device or jax.devices()[0]
    with jax.default_matmul_precision("highest"):
        h, head = _trunk(params, tokens, a, device, dtype=matmul_dtype,
                         router_dtype=router_dtype,
                         taps_reversed=taps_reversed)
        main = _head_losses(head, _f32(params["final_ln"], device), h,
                            jax.device_put(labels, device), eps=a.eps,
                            dtype=matmul_dtype)
    return main, None


def logits(params, tokens, *, arch, device=None):
    """(B, S, V) float32 logits over the held rows: for the small
    sizes of the tests."""
    a = _Arch.of(arch)
    device = device or jax.devices()[0]
    with jax.default_matmul_precision("highest"):
        h, head = _trunk(params, tokens, a, device, dtype=None,
                         router_dtype=None, taps_reversed=False)
        return _rms_norm(h, _f32(params["final_ln"], device), a.eps) @ head.T


def loss(params, tokens, labels, *, arch, device=None):
    """The mean over tokens: what a training step minimises.
    `jax.grad` of it gives the reference's gradient of every leaf (the
    router bias gets none: it only steers the choice; a tied
    embedding's is the sum of both uses)."""
    return jnp.mean(token_losses(params, tokens, labels, arch=arch,
                                 device=device)[0])


def router_weights(p, m, *, arch, device=None, router_dtype=None):
    """(T, E) float32: the weight the router of the expert layer `p`
    gives every published expert for each row of m (T, H), 0 where the
    expert is not among the token's chosen.  m and the router's weights
    are taken at the values handed over (a program's bf16 activations,
    say) and scored in float32; `router_dtype` rounds the scores to
    that dtype, what a program that scored there would compute.  The
    benchmark holds the program's gate to it at the cell's own
    shape."""
    a = _Arch.of(arch)
    device = device or jax.devices()[0]
    p = {k: _f32(p[k], device) for k in ("router", "router_bias")}
    with jax.default_matmul_precision("highest"):
        chosen, weight = jax.jit(_route, static_argnums=(2, 3, 4))(
            p, _f32(m, device), a, None, router_dtype)
    return jnp.sum(jax.nn.one_hot(chosen, p["router"].shape[1])
                   * weight[..., None], axis=1)


def expert_layer(p, m, *, arch):
    """One expert layer alone, for the share test: (T, H) -> (T, H),
    the held experts' part of the routed sum."""
    a = _Arch.of(arch)
    with jax.default_matmul_precision("highest"):
        return _experts(jax.tree.map(lambda w: w.astype(jnp.float32), p),
                        m.astype(jnp.float32), a, None)
