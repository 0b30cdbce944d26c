"""Long-context LM training with ring attention — context parallelism
end to end.

Trains a small causal transformer on ONE packed 32k-token sequence
sharded across all devices on a `cp` mesh axis (8k tokens/device on the
8-way test mesh; the same program scales to 128k+ — see
tests/test_context_parallel.py::test_ring_attention_128k_causal_fwd_bwd).
Demonstrates the full recipe, which the reference cannot express at all
(its FMHA caps at seq 512; SURVEY §5.7):

* zigzag sequence sharding (`zigzag_shard`) so the causal ring's
  per-step work is uniform across devices;
* `ring_attention(layout="zigzag")` inside the model — blockwise flash
  chunks, lse-recompute backward, O(s_local·d) residuals;
* GLOBAL position ids ride through the zigzag permutation, so rotary/
  learned positions and the shifted-label loss stay correct;
* data-parallel-style psum of grads over cp (params replicated),
  FusedAdam on the flat buffer.

Run:  python examples/long_context_training.py --seq 32768 --steps 3
"""

from __future__ import annotations

import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

import argparse
import time

import _bootstrap

_bootstrap.force_cpu_devices_from_argv()

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.optimizers.fused_adam import FusedAdam
from apex_tpu.parallel.context_parallel import ring_attention, zigzag_shard


def parse():
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=32768)
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=2)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--force-cpu-devices", type=int, default=0,
                   help="virtual CPU mesh size (applied at import)")
    return p.parse_args()


def init_params(key, a):
    ks = jax.random.split(key, 2 + 4 * a.layers)
    hd = a.hidden
    params = {
        "embed": jax.random.normal(ks[0], (a.vocab, hd)) * 0.02,
        "pos": jax.random.normal(ks[1], (a.seq, hd)) * 0.02,
    }
    for i in range(a.layers):
        k = ks[2 + 4 * i: 6 + 4 * i]
        params[f"block{i}"] = {
            "qkv": jax.random.normal(k[0], (hd, 3 * hd)) * 0.02,
            "proj": jax.random.normal(k[1], (hd, hd)) * 0.02,
            "fc1": jax.random.normal(k[2], (hd, 4 * hd)) * 0.02,
            "fc2": jax.random.normal(k[3], (4 * hd, hd)) * 0.02,
        }
    return params


def forward_loss(params, tokens, labels, pos_ids, a):
    """Shard-local forward: tokens/labels/pos_ids are (s_local,) zigzag
    shards; attention is the only cross-device op (the ring)."""
    hd, nh = a.hidden, a.heads
    x = params["embed"][tokens] + params["pos"][pos_ids]
    for i in range(a.layers):
        blk = params[f"block{i}"]
        h = _rms(x)
        qkv = h @ blk["qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):  # (s, hd) -> (1, nh, s, hd/nh)
            return t.reshape(-1, nh, hd // nh).transpose(1, 0, 2)[None]

        ctx = ring_attention(heads(q), heads(k), heads(v), "cp",
                             causal=True, layout="zigzag")
        ctx = ctx[0].transpose(1, 0, 2).reshape(-1, hd)
        x = x + ctx @ blk["proj"]
        h = _rms(x)
        x = x + jax.nn.gelu(h @ blk["fc1"], approximate=True) @ blk["fc2"]
    logits = _rms(x) @ params["embed"].T            # tied head (s, V)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1).mean()
    return lax.pmean(nll, "cp")


def _rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-6)


def main():
    a = parse()
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("cp",))
    print(f"cp mesh: {n} devices, {a.seq} tokens "
          f"({a.seq // n}/device, zigzag)")

    key = jax.random.PRNGKey(0)
    params = init_params(key, a)
    opt = FusedAdam(lr=a.lr, use_pallas=False)
    opt_state = opt.init(params)

    # ONE long "document": tokens with local structure so the model has
    # something to learn; labels are the global next-token shift,
    # computed BEFORE the zigzag permutation
    base = jax.random.randint(jax.random.PRNGKey(1), (a.seq,), 0, a.vocab)
    tokens = (base + jnp.roll(base, 1)) % a.vocab   # order-1 structure
    labels = jnp.roll(tokens, -1)
    pos_ids = jnp.arange(a.seq)
    tz, lz, pz = (zigzag_shard(x[None], n, axis=1)[0]
                  for x in (tokens, labels, pos_ids))

    # params live in the flat optimizer state; pull the tree per step
    def step_fn(opt_state, t, l, p_ids):
        from apex_tpu.optimizers import flat as F
        p_tree = F.unflatten(opt_state.params, opt.spec)

        def loss_fn(p):
            return forward_loss(p, t, l, p_ids, a)

        loss, grads = jax.value_and_grad(loss_fn)(p_tree)
        grads = jax.tree_util.tree_map(
            lambda g: lax.pmean(g, "cp"), grads)
        _, opt_state = opt.step(opt_state, grads)
        return opt_state, loss

    step = jax.jit(shard_map(
        step_fn, mesh=mesh,
        in_specs=(P(), P("cp"), P("cp"), P("cp")),
        out_specs=(P(), P()), check_vma=False))

    loss = float("nan")
    for i in range(a.steps):
        t0 = time.perf_counter()
        opt_state, loss = step(opt_state, tz, lz, pz)
        loss = float(loss)
        dt = time.perf_counter() - t0
        print(f"step {i}: loss {loss:.4f}  {dt:.1f}s  "
              f"({a.seq / dt:.0f} tok/s)")
    return loss


if __name__ == "__main__":
    main()
