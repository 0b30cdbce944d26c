"""GPT-2's forward pass and token cross entropy, plainly.

Written from the published description (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners", and the released model
code): learned token and position embeddings; pre-LayerNorm blocks of
causal multi-head self-attention and a 4x MLP with the tanh form of
GELU, each added to the residual stream; a final LayerNorm; logits
through the transposed token embedding; the mean over tokens of
-log softmax at the label.  LayerNorm's epsilon is 1e-5.

Everything is `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")` (on a TPU a float32 matmul
otherwise runs in bf16 passes): no kernel, no cache, no sharding, no
import from `apex_tpu`.  It is handed the weights as the program lays
them out, in the layout one device would see (tensor parallelism 1):

    embed.weight (V, H)   pos_embed (P, H)   final_ln.{weight,bias} (H,)
    block<i>.ln1, .ln2: {weight, bias} (H,)
    block<i>.qkv:  weight (H, 3H), bias (3H,)  columns [q | k | v],
                   each head-major (heads, head_dim)
    block<i>.proj: weight (H, H), bias (H,)
    block<i>.fc1:  weight (H, F), bias (F,)
    block<i>.fc2:  weight (F, H), bias (H,)

Departures from the publication: none in the mathematics.  One layer's
weights are cast to float32 at a time, on `device`, so that checking a
1.3B model costs a few hundred MB and not a second copy of the model.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _f32(tree, device):
    return jax.tree.map(
        lambda a: jax.device_put(a, device).astype(jnp.float32), tree)


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["weight"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames="num_heads")
def _block(p, x, *, num_heads):
    b, s, h = x.shape
    d = h // num_heads
    y = _layer_norm(x, p["ln1"])
    qkv = y @ p["qkv"]["weight"] + p["qkv"]["bias"]
    q, k, v = (a.reshape(b, s, num_heads, d).transpose(0, 2, 1, 3)
               for a in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(b, s, h)
    x = x + ctx @ p["proj"]["weight"] + p["proj"]["bias"]
    y = _layer_norm(x, p["ln2"])
    y = _gelu_tanh(y @ p["fc1"]["weight"] + p["fc1"]["bias"])
    return x + y @ p["fc2"]["weight"] + p["fc2"]["bias"]


@jax.jit
def _embed(p, tokens):
    s = tokens.shape[1]
    return p["embed"][tokens] + p["pos_embed"][:s][None]


@jax.jit
def _head_losses(p, x, labels):
    logits = _layer_norm(x, p["final_ln"]) @ p["embed"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def token_losses(params, tokens, labels, *, num_heads, num_layers, device):
    """(B, S) float32: the cross entropy of every token of `tokens`
    (B, S) against `labels` (B, S) under the network `params`."""
    tokens = jax.device_put(tokens, device)
    labels = jax.device_put(labels, device)
    with jax.default_matmul_precision("highest"):
        ends = _f32({"embed": params["embed"]["weight"],
                     "pos_embed": params["pos_embed"],
                     "final_ln": params["final_ln"]}, device)
        x = _embed(ends, tokens)
        for i in range(num_layers):
            x = _block(_f32(params[f"block{i}"], device), x,
                       num_heads=num_heads)
        return _head_losses(ends, x, labels)
