"""Kimi-Linear's forward pass and token cross entropies over rows of
packed documents, plainly, for the share of the model one chip holds.

Written from the keys of the model's public `config.json`
(`model_type` `kimi_linear`) and from the papers they name: Kimi Delta
Attention for `linear_attn_config` (Kimi Linear, arXiv:2510.26692),
multi-head latent attention for `kv_lora_rank`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim` (DeepSeek-V2, arXiv:2405.04434) and
the DeepSeek-V3 family's expert layer for `num_experts`,
`num_shared_experts`, `moe_renormalize`, `routed_scaling_factor`
(arXiv:2412.19437, 2.1.2).

**The equations.**  Pre-norm blocks, RMSNorm (`rms_norm_eps`), no bias
but KDA's `b_dt`: `h = x + Mixer_i(RMSNorm(x))`, `y = h +
FFN_i(RMSNorm(h))`.  The source counts layers from 1 (`kda_layers` 1,
2, 3, 5, ..., `full_attn_layers` 4, 8, ...); here, from 0, layer `i` is
latent attention where `i + 1` is in `full_attn_layers` and KDA
otherwise.  The FFN of the leading `first_k_dense_replace` layers is a
SwiGLU of `intermediate_size`, of the others the expert layer.  No
rotary embedding and no position table anywhere (`mla_use_nope`).

* Documents.  `eod_token_id` closes a document.  For a row `x_0 ...
  x_(S-1)`, `doc_t` = the number of `s < t` with `x_s` = EOD (the EOD
  belongs to the document it closes), and `t` is a first token where
  `t = 0` or `x_(t-1)` = EOD.  Nothing a mixer computes for token `t`
  depends on a token of another document; norms, FFNs, the router, the
  head and the loss are a token at a time.
* KDA, n = `linear_attn_config.num_heads` heads of d_k = d_v =
  `linear_attn_config.head_dim`: as `solar_open2.py` has it
  (`q, k, v = SiLU(conv(a W_.))`, unit-length q and k, q by d_k^-1/2
  more; `g_t = -exp(A_h) softplus(W_f2 (W_f1 a_t) + b_dt)` a channel;
  **`S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1) + beta_t k_t
  v_t^T`**, `o_t = S_t^T q_t`, a token at a time; `out = W_o
  [RMSNorm_dv(o_t) * sigmoid(W_g2 (W_g1 a_t))]`), with `beta_t =
  sigmoid(W_beta a_t)` in (0, 1) (the source has no
  `kda_allow_neg_eigval`), and with the documents: **at a first token
  `S_(t-1)` is 0, exactly**, and a convolution tap that would read a
  token of another document reads 0.
* latent attention without positions and without a query compression
  (`q_lora_rank` null): `q = a W_q` (heads x (nope + rope));
  `[c_kv | k_s] = a W_kva`, `c_kv = RMSNorm(c_kv)`; `[k_n | v] = c_kv
  W_kvb` a head; head h's key is `[k_n,h | k_s]`, the `rope`-wide row
  shared by all heads, neither it nor q rotated; `softmax(q k^T /
  sqrt(nope + rope)) v` over the pairs `s <= t` with `doc_s = doc_t`;
  `out = ctx W_o`.
* the expert layer: `s = sigmoid(m W_r)` over all the published
  experts; the `num_experts_per_token` largest of `s + b` are chosen
  (`b` for the choice only; one group); weights `routed_scaling_factor
  * s_e / (sum of the chosen s + 1e-20)`; `y = sum_chosen w_e
  SwiGLU_e(m) + SwiGLU_shared(m)`, no token dropped.
* loss: cross entropy over the held rows of the untied head, every
  position (the label of an EOD position is the next document's first
  token).

**The share.**  `arch` (the benchmark's configuration file) says what
is held: `num_hidden_layers` layers, experts `[experts_first,
experts_first + num_experts)` of the `num_experts_published` the
router scores, `vocab_size` rows of embedding and head.  What the
experts held elsewhere would add to a token is left out.  Given every
expert this is the uncut layer.

Everything is `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernel, no sharding, no
import from `apex_tpu`; the small pieces it has in common with
`solar_open2.py` (the norm, the SwiGLU, the expert layer, the head) are
that file's.  Attention runs a block of queries at a time as a dense
masked softmax.  Weights are handed over as the program lays them out:

    embed.weight, head.weight (V, H)   final_ln.weight (H,)
    block<i>.ln1.weight, .ln2.weight (H,)
    block<i>.attn, latent: q (H, heads (nope + rope)), kv_a (H, rank +
        rope), kv_a_norm.weight (rank,), kv_b (rank, heads (nope + v)),
        proj (heads v, H); a head's columns lie together
    block<i>.attn, KDA: as in `solar_open2.py`
    block<i>.mlp, dense: gate_up (H, 2f), down (f, H)
    block<i>.mlp, experts: as in `solar_open2.py`
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.solar_open2 import (
    _experts,
    _f32,
    _head_losses,
    _mm,
    _rms_norm,
    _rounded,
    _swiglu,
)

Q_BLOCK = 256        # queries a block of attention scores


class _Arch(NamedTuple):
    """The sizes the forward needs (hashable: a static argument); the
    expert layer's fields under the names `solar_open2._experts`
    reads."""

    heads: int
    nope: int
    rope: int
    v: int
    kv_rank: int
    kda_heads: int
    kda_dim: int
    attends: tuple
    dense: int
    eps: float
    layers: int
    top_k: int
    first: int
    held: int
    scale: float
    renormalize: bool
    eod: int

    @classmethod
    def of(cls, arch):
        """From the configuration's keys; an _Arch as it is."""
        if isinstance(arch, cls):
            return arch
        linear = arch["linear_attn_config"]
        return cls(
            heads=int(arch["num_attention_heads"]),
            nope=int(arch["qk_nope_head_dim"]),
            rope=int(arch["qk_rope_head_dim"]), v=int(arch["v_head_dim"]),
            kv_rank=int(arch["kv_lora_rank"]),
            kda_heads=int(linear["num_heads"]),
            kda_dim=int(linear["head_dim"]),
            # the source counts layers from 1
            attends=tuple(int(i) - 1 for i in linear["full_attn_layers"]),
            dense=int(arch["first_k_dense_replace"]),
            eps=float(arch["rms_norm_eps"]),
            layers=int(arch["num_hidden_layers"]),
            top_k=int(arch["num_experts_per_token"]),
            first=int(arch.get("experts_first", 0)),
            held=int(arch["num_experts"]),
            scale=float(arch["routed_scaling_factor"]),
            renormalize=bool(arch["moe_renormalize"]),
            eod=int(arch["eod_token_id"]))


def documents(tokens, eod: int, boundaries: bool = True):
    """(doc (B, S) int32, first (B, S) bool) of `tokens`; one document
    a row where `boundaries` is false (the EOD an ordinary token)."""
    is_eod = (tokens == eod) & boundaries
    doc = jnp.cumsum(is_eod, axis=1) - is_eod
    first = jnp.concatenate([jnp.ones_like(is_eod[:, :1]), is_eod[:, :-1]], 1)
    return doc.astype(jnp.int32), first


def _latent_attention(p, a, doc, arch, dtype):
    b, s, _ = a.shape
    nh, dn, dr, dv = arch.heads, arch.nope, arch.rope, arch.v
    q = _mm(a, p["q"], dtype).reshape(b, s, nh, dn + dr)
    ckv = _mm(a, p["kv_a"], dtype)
    c_kv = _rms_norm(ckv[..., :arch.kv_rank], p["kv_a_norm"], arch.eps)
    k_s = ckv[..., None, arch.kv_rank:]                     # (B, S, 1, dr)
    kv = _mm(c_kv, p["kv_b"], dtype).reshape(b, s, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_s, (b, s, nh, dr))], -1)
    v = kv[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)
    blocks = []
    for start in range(0, s, Q_BLOCK):       # a block of queries at a time
        stop = min(start + Q_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk",
                            _rounded(q[:, start:stop], dtype),
                            _rounded(k[:, :stop], dtype)) * scale
        keep = ((jnp.arange(start, stop)[:, None] >= jnp.arange(stop)[None])
                & (doc[:, start:stop, None] == doc[:, None, :stop]))
        probs = jax.nn.softmax(
            jnp.where(keep[:, None], scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("bhqk,bkhd->bqhd", _rounded(probs, dtype),
                                 _rounded(v[:, :stop], dtype)))
    ctx = jnp.concatenate(blocks, axis=1).reshape(b, s, nh * dv)
    return _mm(ctx, p["proj"], dtype)


def _conv_silu(x, w, doc):
    """x (B, S, C), w (taps, C): y_t = sum_r w_(taps-1-r) x_(t-r) over
    the taps whose token t - r is in the row and of t's document."""
    taps = w.shape[0]
    y = 0.0
    for r in range(taps):
        back = jnp.pad(x, ((0, 0), (r, 0), (0, 0)))[:, :x.shape[1]]
        same = jnp.pad(doc, ((0, 0), (r, 0)), constant_values=-1)[
            :, :doc.shape[1]] == doc
        y = y + jnp.where(same[..., None], back, 0.0) * w[taps - 1 - r]
    return jax.nn.silu(y)


def delta_rule(q, k, v, g, beta, first, state_dtype=None):
    """The recurrence of a head, a token at a time, the state set to 0
    before every token `first` (B, S) marks.  q, k, g (B, S, n, d_k), v
    (B, S, n, d_v), beta (B, S, n) -> o (B, S, n, d_v).  `state_dtype`
    rounds the state after every token."""

    def token(state, x):
        qt, kt, vt, gt, bt, ft = x               # (B, n, d) ... (B, n), (B,)
        state = jnp.where(ft[:, None, None, None], 0.0, state)
        state = state * jnp.exp(gt)[..., None]
        seen = jnp.einsum("bnkv,bnk->bnv", state, kt)
        state = state + jnp.einsum("bnk,bnv->bnkv", kt,
                                   bt[..., None] * (vt - seen))
        state = _rounded(state, state_dtype)
        return state, jnp.einsum("bnkv,bnk->bnv", state, qt)

    b, _, n, dk = q.shape
    zero = jnp.zeros((b, n, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(token, zero, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta, first)))
    return jnp.moveaxis(o, 0, 1)


def scan_outputs(q, k, v, g, beta, first, *, device=None, state_dtype=None):
    """`delta_rule` over head-major arrays, as a program's chunked op
    takes them: q, k, g (B, n, S, d_k), v (B, n, S, d_v), beta (B, n,
    S), of any float dtype, `first` (B, S) bool -> o (B, n, S, d_v)
    float32."""
    device = device or jax.devices()[0]
    args = [jnp.moveaxis(jax.device_put(x, device).astype(jnp.float32), 1, 2)
            for x in (q, k, v, g, beta)]
    with jax.default_matmul_precision("highest"):
        o = jax.jit(delta_rule, static_argnames="state_dtype")(
            *args, jax.device_put(first, device), state_dtype=state_dtype)
    return jnp.moveaxis(o, 2, 1)


def _kda(p, a, doc, first, arch, dtype, state_dtype):
    b, s, _ = a.shape
    n, d = arch.kda_heads, arch.kda_dim
    q, k, v = (_conv_silu(_mm(a, p[x], dtype), p["conv_" + x], doc
                          ).reshape(b, s, n, d) for x in "qkv")
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    f = _mm(_mm(a, p["f_a"], dtype), p["f_b"], dtype) + p["dt_bias"]
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(f).reshape(b, s, n, d)
    beta = jax.nn.sigmoid(_mm(a, p["beta"], dtype))
    o = delta_rule(q, k, v, g, beta, first, state_dtype)
    o = _rms_norm(o, p["o_norm"], arch.eps).reshape(b, s, n * d)
    gate = _mm(_mm(a, p["g_a"], dtype), p["g_b"], dtype)
    return _mm(o * jax.nn.sigmoid(gate), p["proj"], dtype)


@functools.partial(jax.jit, static_argnames=(
    "arch", "attends", "dense", "dtype", "state_dtype"))
def _block(p, x, doc, first, *, arch, attends, dense, dtype, state_dtype):
    a = _rms_norm(x, p["ln1"], arch.eps)
    x = x + (_latent_attention(p["attn"], a, doc, arch, dtype) if attends
             else _kda(p["attn"], a, doc, first, arch, dtype, state_dtype))
    m = _rms_norm(x, p["ln2"], arch.eps)
    if dense:
        return x + _swiglu(m, p["mlp"]["gate_up"], p["mlp"]["down"], dtype)
    b, s, h = m.shape
    return x + _experts(p["mlp"], m.reshape(b * s, h), arch,
                        dtype).reshape(b, s, h)


def token_losses(params, tokens, labels, *, arch, device=None,
                 matmul_dtype=None, state_dtype=None, boundaries=True):
    """(main, None): (B, S) float32 cross entropies of every token of
    `tokens` (B, S) against `labels` under the network `params` and the
    share `arch` describes; None for the second head another job's
    model has.

    `matmul_dtype` rounds both operands of every matrix product to that
    dtype first, `state_dtype` the KDA state after every token; with
    `boundaries` false the EOD is an ordinary token and a row one
    document: what a program that forgot its boundaries computes.  The
    benchmark reads its tolerances against them."""
    a = _Arch.of(arch)
    device = device or jax.devices()[0]
    tokens = jax.device_put(tokens, device)
    labels = jax.device_put(labels, device)
    doc, first = documents(tokens, a.eod, boundaries)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"]["weight"], device)[tokens]
        for i in range(a.layers):
            h = _block(_f32(params[f"block{i}"], device), h, doc, first,
                       arch=a, attends=i in a.attends, dense=i < a.dense,
                       dtype=matmul_dtype, state_dtype=state_dtype)
        main = _head_losses(_f32(params["head"]["weight"], device),
                            _f32(params["final_ln"], device), h, labels,
                            eps=a.eps, dtype=matmul_dtype)
    return main, None


def loss(params, tokens, labels, *, arch, device=None):
    """The mean over every position: what a training step minimises.
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
