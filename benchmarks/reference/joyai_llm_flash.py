"""JoyAI-LLM-Flash's forward pass, token cross entropies and loss,
plainly, for the share of the model one chip holds.

Written from the keys of the model's public `config.json`
(`model_type` `joyai_llm_flash`; they are the DeepSeek-V3 family's) and
from that family's published description (DeepSeek-V3 technical report,
arXiv:2412.19437, sections 2.1.1 latent attention, 2.1.2 the expert
layer with the bias-corrected choice, 2.2 multi-token prediction):

* block: `h += MLA(RMSNorm(h))`, `h += FFN(RMSNorm(h))`; RMSNorm's
  epsilon is `rms_norm_eps`; no bias anywhere;
* MLA: `c_q = RMSNorm(a W_qa)`; `q = c_q W_qb`, per head
  (`qk_nope_head_dim` | `qk_rope_head_dim`); `[c_kv | k_r] = a W_kva`
  (`kv_lora_rank` | rope), `c_kv = RMSNorm(c_kv)`; `[k_nope | v] =
  c_kv W_kvb` per head (nope | `v_head_dim`); rotary embedding with
  base `rope_theta` on `q_r` and on the one `k_r` all heads share,
  pairs (2i, 2i+1) turned together (`rope_interleave`), written with
  complex numbers; `o = softmax(causal(q k^T / sqrt(nope + rope))) v`;
  the output projection;
* FFN of the first `first_k_dense_replace` layers: `(silu(m W_g) *
  (m W_u)) W_d`; of the others: `s = sigmoid(m W_r)` over all the
  published experts; the `num_experts_per_tok` experts with the largest
  `s + b` are chosen (`b` = `e_score_correction_bias`, for the choice
  only; one group, so no group limit); weights `g_e =
  routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)`;
  `y = sum_chosen g_e FFN_e(m) + FFN_shared(m)`, no token dropped;
* multi-token prediction, one module: `h' = W_eh [RMSNorm(h_i) ;
  RMSNorm(E[t_{i+1}])]`, one more expert block, a norm, the main
  model's embedding and head, cross entropy against `t_{i+2}`;
  `loss = mean(L_main) + mtp_loss_weight * mean(L_mtp)`.

**The share.**  `arch` (the benchmark's configuration file) says what
is held: `num_hidden_layers` layers of which the first
`first_k_dense_replace` are dense, experts `[experts_first,
experts_first + n_routed_experts)` of the `n_routed_experts_published`
the router scores, `vocab_size` rows of embedding and head.  The
router keeps its published width and its experts per token; what the
experts held elsewhere would add to a token is left out, and that
partial result goes on to the next layer; logits and the loss are over
the held rows.  Given every expert (`experts_first` 0,
`n_routed_experts` = the published count) this is the uncut layer.

Everything is `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernel, no sharding, no
import from `apex_tpu`.  Attention runs a block of queries at a time
and the head a sequence at a time, so that two sequences of 4096 fit
beside the system under test.  Weights are handed over as the program
lays them out (tensor parallelism 1):

    embed.weight, head.weight (V, H)   final_ln.weight (H,)
    block<i>.ln1.weight, .ln2.weight (H,)
    block<i>.attn: q_a (H, q_lora_rank), q_a_norm.weight, q_b
        (q_lora_rank, heads * (nope + rope)), kv_a (H, kv_lora_rank +
        rope), kv_a_norm.weight, kv_b (kv_lora_rank, heads * (nope +
        v)), proj (heads * v, H); a head's columns lie together
    block<i>.mlp, dense: gate_up (H, 2F) = [W_g | W_u], down (F, H)
    block<i>.mlp, experts: router (H, E), router_bias (E,),
        experts_gate_up (held, H, 2f), experts_down (held, f, H),
        shared_gate_up (H, 2f), shared_down (f, H)
    mtp: hnorm.weight, enorm.weight, proj (2H, H), final_ln.weight;
        its block is block<num_hidden_layers>

Departures from the published description, each an assumption the
configuration file lists under `assumed`:
* the order inside W_eh's input is [hidden ; embedding], as the
  report's equation 21 writes it (released inference code concatenates
  the other way round; with random weights the two are one model up to
  a permutation of W_eh's rows);
* the hidden state that feeds the MTP module is the last held layer's
  residual stream *before* the main model's final norm (the report's
  h_i^{0} "given by the main model"; the module norms it itself);
* `mtp_loss_weight` (the report's lambda) is 0.3, its value for the
  first 10T tokens; `config.json` does not carry it;
* the MTP target of a sequence's last position is taken from `labels`
  rolled by one, as the benchmark's seeded batches give the main head
  its last label: synthetic data has no document end to mask.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

Q_BLOCK = 512        # queries a block of attention scores


def _f32(tree, device):
    return jax.tree.map(
        lambda a: jax.device_put(a, device).astype(jnp.float32), tree)


class _Arch(NamedTuple):
    """The sizes the forward needs (hashable: a static argument)."""

    heads: int
    nope: int
    rope: int
    v: int
    kv_rank: int
    theta: float
    eps: float
    layers: int
    dense: int
    mtp: bool
    top_k: int
    first: int
    held: int
    scale: float
    renormalize: bool
    mtp_weight: float

    @classmethod
    def of(cls, arch):
        """From the configuration's keys; an _Arch as it is."""
        if isinstance(arch, cls):
            return arch
        return cls(
            heads=int(arch["num_attention_heads"]),
            nope=int(arch["qk_nope_head_dim"]),
            rope=int(arch["qk_rope_head_dim"]), v=int(arch["v_head_dim"]),
            kv_rank=int(arch["kv_lora_rank"]),
            theta=float(arch["rope_theta"]), eps=float(arch["rms_norm_eps"]),
            layers=int(arch["num_hidden_layers"]),
            dense=int(arch["first_k_dense_replace"]),
            mtp=int(arch.get("num_nextn_predict_layers", 0)) > 0,
            top_k=int(arch["num_experts_per_tok"]),
            first=int(arch.get("experts_first", 0)),
            held=int(arch["n_routed_experts"]),
            scale=float(arch["routed_scaling_factor"]),
            renormalize=bool(arch["norm_topk_prob"]),
            mtp_weight=float(arch.get("mtp_loss_weight", 0.3)))


def _rounded(x, dtype):
    """x as a GEMM in `dtype` sees it, still float32; x where None."""
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _mm(a, b, dtype):
    """a @ b, both operands rounded to `dtype` first, the product
    float32."""
    return _rounded(a, dtype) @ _rounded(b, dtype)


def _rms_norm(x, p, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * p["weight"]


def _rope(x, theta):
    """x (B, S, n, d): pairs (2i, 2i+1) as complex numbers, turned by
    position * theta^(-2i/d)."""
    b, s, n, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq  # (S, d/2)
    turn = jnp.exp(1j * angle.astype(jnp.complex64))[None, :, None, :]
    pairs = x.reshape(b, s, n, d // 2, 2)
    z = (pairs[..., 0] + 1j * pairs[..., 1]) * turn
    return jnp.stack([z.real, z.imag], axis=-1).reshape(b, s, n, d)


def _swiglu(x, w_gate_up, w_down, dtype):
    gate, up = jnp.split(_mm(x, w_gate_up, dtype), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, w_down, dtype)


def _attention(p, a, arch, dtype):
    b, s, _ = a.shape
    nh, dn, dr, dv = arch.heads, arch.nope, arch.rope, arch.v
    c_q = _rms_norm(_mm(a, p["q_a"], dtype), p["q_a_norm"], arch.eps)
    q = _mm(c_q, p["q_b"], dtype).reshape(b, s, nh, dn + dr)
    ckv = _mm(a, p["kv_a"], dtype)
    c_kv = _rms_norm(ckv[..., :arch.kv_rank], p["kv_a_norm"], arch.eps)
    k_r = _rope(ckv[..., None, arch.kv_rank:], arch.theta)   # (B, S, 1, dr)
    kv = _mm(c_kv, p["kv_b"], dtype).reshape(b, s, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], arch.theta)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (b, s, nh, dr))], -1)
    v = kv[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)
    blocks = []
    for start in range(0, s, Q_BLOCK):       # a block of queries at a time
        stop = min(start + Q_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk",
                            _rounded(q[:, start:stop], dtype),
                            _rounded(k[:, :stop], dtype)) * scale
        causal = (jnp.arange(start, stop)[:, None]
                  >= jnp.arange(stop)[None, :])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("bhqk,bkhd->bqhd", _rounded(probs, dtype),
                                 _rounded(v[:, :stop], dtype)))
    ctx = jnp.concatenate(blocks, axis=1).reshape(b, s, nh * dv)
    return _mm(ctx, p["proj"], dtype)


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


@functools.partial(jax.jit, static_argnames=("arch", "dense", "dtype"))
def _block(p, x, *, arch, dense, dtype):
    x = x + _attention(p["attn"], _rms_norm(x, p["ln1"], arch.eps), arch,
                       dtype)
    m = _rms_norm(x, p["ln2"], arch.eps)
    if dense:
        return x + _swiglu(m, p["mlp"]["gate_up"], p["mlp"]["down"], dtype)
    b, s, h = m.shape
    return x + _experts(p["mlp"], m.reshape(b * s, h), arch,
                        dtype).reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head_losses(head, norm, x, labels, *, eps, dtype):
    def one(args):                          # a sequence at a time
        xs, ls = args
        logits = _mm(_rms_norm(xs, norm, eps), head.T, dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, ls[:, None], axis=-1)[:, 0]
    return jax.lax.map(one, (x, labels))


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _mtp_input(p, embed, h, next_tokens, *, eps, dtype):
    both = jnp.concatenate([_rms_norm(h, p["hnorm"], eps),
                            _rms_norm(embed[next_tokens], p["enorm"], eps)],
                           axis=-1)
    return _mm(both, p["proj"], dtype)


def token_losses(params, tokens, labels, *, arch, device=None,
                 matmul_dtype=None):
    """(main, mtp): (B, S) float32 cross entropies of every token of
    `tokens` (B, S), of the main head against `labels` and of the MTP
    head against `labels` rolled by one (None without the module),
    under the network `params` and the share `arch` describes.

    `matmul_dtype` rounds both operands of every matrix product to that
    dtype first: what the same network computes with GEMMs of that
    precision.  The benchmark reads its tolerances against it."""
    a = _Arch.of(arch)
    device = device or jax.devices()[0]
    tokens = jax.device_put(tokens, device)
    labels = jax.device_put(labels, device)
    kw = dict(eps=a.eps, dtype=matmul_dtype)
    with jax.default_matmul_precision("highest"):
        embed = _f32(params["embed"]["weight"], device)
        head = _f32(params["head"]["weight"], device)
        h = embed[tokens]
        for i in range(a.layers):
            h = _block(_f32(params[f"block{i}"], device), h, arch=a,
                       dense=i < a.dense, dtype=matmul_dtype)
        main = _head_losses(head, _f32(params["final_ln"], device), h,
                            labels, **kw)
        if not a.mtp:
            return main, None
        p = _f32(params["mtp"], device)
        x = _mtp_input(p, embed, h, labels, **kw)
        x = _block(_f32(params[f"block{a.layers}"], device), x, arch=a,
                   dense=False, dtype=matmul_dtype)
        mtp = _head_losses(head, p["final_ln"], x,
                           jnp.roll(labels, -1, axis=1), **kw)
        return main, mtp


def loss(params, tokens, labels, *, arch, device=None):
    """`mean(L_main) + mtp_loss_weight * mean(L_mtp)`: what a training
    step minimises.  `jax.grad` of it gives the reference's gradient of
    every leaf (the router bias gets none: it only steers the choice)."""
    a = _Arch.of(arch)
    main, mtp = token_losses(params, tokens, labels, arch=a, device=device)
    total = jnp.mean(main)
    if mtp is not None:
        total = total + a.mtp_weight * jnp.mean(mtp)
    return total


def expert_layer(p, m, *, arch):
    """One expert layer alone, for the share test: (T, H) -> (T, H),
    the held experts' part of the routed sum plus the shared expert."""
    a = _Arch.of(arch)
    with jax.default_matmul_precision("highest"):
        return _experts(jax.tree.map(lambda w: w.astype(jnp.float32), p),
                        m.astype(jnp.float32), a, None)
