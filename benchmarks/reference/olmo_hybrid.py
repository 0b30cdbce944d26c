"""Olmo-Hybrid's forward pass and token cross entropies over rows of
packed documents, plainly, for the share of the model one chip holds.

Written from the keys of the model's public `config.json`
(`model_type` `olmo_hybrid`) and from the papers they name: Gated
DeltaNet for the `linear_*` keys (Yang et al., arXiv:2412.06464, in the
form of FLA's `GatedDeltaNet`: `linear_allow_neg_eigval` doubles beta),
OLMo 2's reordered norm and QK norm for the block (arXiv:2501.00656).

**The equations.**  Post-normed blocks, RMSNorm (`rms_norm_eps`), no
bias but the decay's `dt_bias`: `h = x + RMSNorm(Mixer_i(x))`, `y = h +
RMSNorm(FFN(h))`, no norm on a sublayer's input, a final RMSNorm before
the untied head.  Layer `i` is full attention where `layer_types[i]` is
`full_attention`, Gated DeltaNet otherwise; every FFN is `W_2 (SiLU(W_1
h) * W_3 h)` of `intermediate_size`.  No rotary embedding and no
position table (`rope_theta` null): the convolutions and the recurrence
carry position.

* Documents, as `kimi_linear.py` has them: `eod_token_id` closes one;
  nothing a mixer computes for token `t` depends on a token of another
  document; norms, FFNs, the head and the loss are a token at a time.
* Full attention, `num_attention_heads` heads of `hidden_size / heads`:
  `q = RMSNorm(x W_q)`, `k = RMSNorm(x W_k)`, each norm over the whole
  projection; `v = x W_v`; `softmax(q k^T / sqrt(d)) v` over the pairs
  `s <= t` with `doc_s = doc_t`; `out = ctx W_o`.
* Gated DeltaNet, n = `linear_num_key_heads` heads, keys
  `linear_key_head_dim` and values `linear_value_head_dim` wide: `q, k,
  v = SiLU(conv(x W_.))`, a causal depthwise convolution of
  `linear_conv_kernel_dim` taps without bias whose taps read 0 across a
  document's start; q and k of unit length a head, q by d_k^-1/2 more;
  **one log-decay a head and token** `g_t = -exp(A_log) softplus(x_t W_a
  + dt_bias)`; `beta_t = 2 sigmoid(x_t W_b)`; **`S_t = exp(g_t) (I -
  beta_t k_t k_t^T) S_(t-1) + beta_t k_t v_t^T`** a token at a time,
  `S_(t-1)` = 0 at a document's first token, `o_t = S_t^T q_t`; `out =
  W_o [RMSNorm_dv(o_t) * SiLU(x_t W_g)]`, the norm's weight shared by
  the heads.  (The recurrence is written as the program's op defines
  it, decay first: `exp(g) (I - beta k k^T) S = (I - beta k k^T)
  exp(g) S` for a scalar g.)

Departures, noted: none in the mathematics; the model's own modelling
file was not at hand (no network), so the gate's and norm's form, the
convolution without bias and the norm placement are the readings the
configuration file lists under `assumed`.

Everything is `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernel, no sharding, no
import from `apex_tpu`.  Attention runs a block of queries at a time
against every key as a dense masked softmax; a run of layers of one
kind is a scan over their weights.  Under `jax.grad` a layer, a block
of queries, the head and a span of `REMAT_TOKENS` tokens of the
recurrence are each recomputed in the backward (`jax.checkpoint`), so
that a gradient at 8,192 tokens keeps a layer's input and a state a
span, not a state a token: the forward's numbers are the same.  Weights are handed over as
the program lays them out:

    embed.weight, head.weight (V, H)   final_ln.weight (H,)
    block<i>.ln1.weight, .ln2.weight (H,): the mixer's and the FFN's
        post-norms
    block<i>.attn, full: q, k, v (H, H), q_norm.weight, k_norm.weight
        (H,), proj (H, H); a head's columns lie together
    block<i>.attn, Gated DeltaNet: q, k (H, n d_k), v (H, n d_v),
        conv_q, conv_k (taps, n d_k), conv_v (taps, n d_v), a (H, n),
        a_log, dt_bias (n,), beta (H, n), gate (H, n d_v),
        o_norm.weight (d_v,), proj (n d_v, H); tap j weighs the token
        taps - 1 - j back
    block<i>.mlp: gate_up (H, 2f), down (f, H)
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.kimi_linear import Q_BLOCK, _conv_silu, documents
from benchmarks.reference.solar_open2 import (
    _f32,
    _head_losses,
    _mm,
    _rms_norm,
    _rounded,
    _swiglu,
)

REMAT_TOKENS = 64    # tokens of the recurrence a checkpoint spans


class _Arch(NamedTuple):
    """The sizes the forward needs (hashable: a static argument)."""

    heads: int
    head_dim: int
    gdn_heads: int
    key_dim: int
    value_dim: int
    neg_eigval: bool
    attends: tuple
    eps: float
    layers: int
    eod: int

    @classmethod
    def of(cls, arch):
        """From the configuration's keys; an _Arch as it is."""
        if isinstance(arch, cls):
            return arch
        layers = int(arch["num_hidden_layers"])
        heads = int(arch["num_attention_heads"])
        return cls(
            heads=heads, head_dim=int(arch["hidden_size"]) // heads,
            gdn_heads=int(arch["linear_num_key_heads"]),
            key_dim=int(arch["linear_key_head_dim"]),
            value_dim=int(arch["linear_value_head_dim"]),
            neg_eigval=bool(arch["linear_allow_neg_eigval"]),
            attends=tuple(i for i, kind in enumerate(
                arch["layer_types"][:layers]) if kind == "full_attention"),
            eps=float(arch["rms_norm_eps"]), layers=layers,
            eod=int(arch["eod_token_id"]))


def _attention(p, x, doc, arch, dtype):
    b, s, _ = x.shape
    nh, d = arch.heads, arch.head_dim
    q = _rms_norm(_mm(x, p["q"], dtype), p["q_norm"], arch.eps)
    k = _rms_norm(_mm(x, p["k"], dtype), p["k_norm"], arch.eps)
    q, k, v = (y.reshape(b, s, nh, d) for y in (q, k, _mm(x, p["v"], dtype)))
    scale = 1.0 / math.sqrt(d)
    per = math.gcd(s, Q_BLOCK)

    @jax.checkpoint
    def queries(start):                      # a block of queries at a time
        at = start + jnp.arange(per)
        keep = ((at[:, None] >= jnp.arange(s)[None])
                & (doc[:, at, None] == doc[:, None, :]))
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk",
            _rounded(jax.lax.dynamic_slice_in_dim(q, start, per, 1), dtype),
            _rounded(k, dtype)) * scale
        probs = jax.nn.softmax(
            jnp.where(keep[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _rounded(probs, dtype),
                          _rounded(v, dtype))

    ctx = jax.lax.map(queries, jnp.arange(0, s, per))   # (s / per, b, ...)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, nh * d)
    return _mm(ctx, p["proj"], dtype)


def delta_rule(q, k, v, g, beta, first, state_dtype=None, decay_dtype=None):
    """The recurrence of a head, a token at a time, the state set to 0
    before every token `first` (B, S) marks.  q, k (B, S, n, d_k), v
    (B, S, n, d_v), g and beta (B, S, n) -> o (B, S, n, d_v).
    `state_dtype` rounds the state after every token, `decay_dtype` a
    token's decay factor exp(g) before it is applied."""

    def token(state, x):
        qt, kt, vt, gt, bt, ft = x               # (B, n, d) ... (B, n), (B,)
        state = jnp.where(ft[:, None, None, None], 0.0, state)
        state = state * _rounded(jnp.exp(gt), decay_dtype)[..., None, None]
        seen = jnp.einsum("bnkv,bnk->bnv", state, kt)
        state = state + jnp.einsum("bnk,bnv->bnkv", kt,
                                   bt[..., None] * (vt - seen))
        state = _rounded(state, state_dtype)
        return state, jnp.einsum("bnkv,bnk->bnv", state, qt)

    @jax.checkpoint
    def span(state, xs):                     # REMAT_TOKENS tokens
        return jax.lax.scan(token, state, xs)

    b, s, n, dk = q.shape
    zero = jnp.zeros((b, n, dk, v.shape[-1]), jnp.float32)
    per = math.gcd(s, REMAT_TOKENS)
    _, o = jax.lax.scan(span, zero, tuple(
        jnp.moveaxis(x, 1, 0).reshape(s // per, per, *x.shape[:1],
                                      *x.shape[2:])
        for x in (q, k, v, g, beta, first)))
    return jnp.moveaxis(o.reshape(s, *o.shape[2:]), 0, 1)


def scan_outputs(q, k, v, g, beta, first, *, device=None, state_dtype=None,
                 decay_dtype=None):
    """`delta_rule` over head-major arrays, as a program's chunked op
    takes them: q, k (B, n, S, d_k), v (B, n, S, d_v), g and beta (B,
    n, S), of any float dtype, `first` (B, S) bool -> o (B, n, S, d_v)
    float32."""
    device = device or jax.devices()[0]
    args = [jnp.moveaxis(jax.device_put(x, device).astype(jnp.float32), 1, 2)
            for x in (q, k, v, g, beta)]
    with jax.default_matmul_precision("highest"):
        o = jax.jit(delta_rule, static_argnames=(
            "state_dtype", "decay_dtype"))(
            *args, jax.device_put(first, device), state_dtype=state_dtype,
            decay_dtype=decay_dtype)
    return jnp.moveaxis(o, 2, 1)


def _gdn(p, x, doc, first, arch, dtype, state_dtype):
    b, s, _ = x.shape
    n, dk, dv = arch.gdn_heads, arch.key_dim, arch.value_dim
    q, k, v = (_conv_silu(_mm(x, p[w], dtype), p["conv_" + w], doc
                          ).reshape(b, s, n, d)
               for w, d in zip("qkv", (dk, dk, dv)))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
        _mm(x, p["a"], dtype) + p["dt_bias"])
    beta = jax.nn.sigmoid(_mm(x, p["beta"], dtype))
    if arch.neg_eigval:
        beta = 2.0 * beta
    o = delta_rule(q, k, v, g, beta, first, state_dtype)
    o = _rms_norm(o, p["o_norm"], arch.eps).reshape(b, s, n * dv)
    return _mm(o * jax.nn.silu(_mm(x, p["gate"], dtype)), p["proj"], dtype)


@functools.partial(jax.jit, static_argnames=(
    "arch", "attends", "dtype", "state_dtype"))
def _block(p, x, doc, first, *, arch, attends, dtype, state_dtype):
    y = (_attention(p["attn"], x, doc, arch, dtype) if attends
         else _gdn(p["attn"], x, doc, first, arch, dtype, state_dtype))
    x = x + _rms_norm(y, p["ln1"], arch.eps)
    y = _swiglu(x, p["mlp"]["gate_up"], p["mlp"]["down"], dtype)
    return x + _rms_norm(y, p["ln2"], arch.eps)


def token_losses(params, tokens, labels, *, arch, device=None,
                 matmul_dtype=None, state_dtype=None, boundaries=True):
    """(main, None): (B, S) float32 cross entropies of every token of
    `tokens` (B, S) against `labels` under the network `params` and the
    share `arch` describes; None for the second head another job's
    model has.

    `matmul_dtype` rounds both operands of every matrix product to that
    dtype first, `state_dtype` the delta rule's state after every
    token; with `boundaries` false the EOD is an ordinary token and a
    row one document.  The benchmark reads its tolerances against
    them."""
    a = _Arch.of(arch)
    device = device or jax.devices()[0]
    tokens = jax.device_put(tokens, device)
    labels = jax.device_put(labels, device)
    doc, first = documents(tokens, a.eod, boundaries)
    return _losses(params, tokens, labels, doc, first, arch=a,
                   device=device, matmul_dtype=matmul_dtype,
                   state_dtype=state_dtype), None


@functools.partial(jax.jit, static_argnames=(
    "arch", "device", "matmul_dtype", "state_dtype"))
def _losses(params, tokens, labels, doc, first, *, arch, device,
            matmul_dtype, state_dtype):
    a = arch
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"]["weight"], device)[tokens]
        i = 0
        while i < a.layers:
            # a run of layers of one kind is one scan over their weights,
            # its layer compiled once; the float32 copy is made inside
            # the checkpoint: a gradient keeps the weights as they came
            attends = i in a.attends
            j = next((j for j in range(i + 1, a.layers)
                      if (j in a.attends) != attends), a.layers)
            layer = jax.checkpoint(functools.partial(
                lambda p, h, attends: _block(
                    _f32(p, device), h, doc, first, arch=a,
                    attends=attends, dtype=matmul_dtype,
                    state_dtype=state_dtype), attends=attends))
            h = jax.lax.scan(lambda h, p: (layer(p, h), None), h,
                             jax.tree.map(lambda *x: jnp.stack(x), *(
                                 params[f"block{k}"] for k in range(i, j))
                                          ))[0]
            i = j
        return jax.checkpoint(lambda w, norm, h: _head_losses(
            _f32(w, device), _f32(norm, device), h, labels, eps=a.eps,
            dtype=matmul_dtype))(params["head"]["weight"],
                                 params["final_ln"], h)


def loss(params, tokens, labels, *, arch, device=None, matmul_dtype=None):
    """The mean over every position: what a training step minimises.
    `jax.grad` of it gives the reference's gradient of every leaf, in
    the leaves' own dtype, computed in float32 (`matmul_dtype` as
    `token_losses` takes it)."""
    return jnp.mean(token_losses(params, tokens, labels, arch=arch,
                                 device=device,
                                 matmul_dtype=matmul_dtype)[0])
