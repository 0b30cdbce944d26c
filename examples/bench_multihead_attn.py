"""Fused multihead-attention latency benchmark.

≡ apex/contrib/examples/multihead_attn/perf_test_multihead_attn.py:
101-110 — fwd and fwd+bwd latency of the fused self-attention module vs
an unfused jnp reference, on one chip.

Run:  python examples/bench_multihead_attn.py [--seq 1024] [--batch 8]
"""

from __future__ import annotations

import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn


def timeit(f, *args, iters=20):
    for _ in range(3):
        r = f(*args)
    np.asarray(jax.tree_util.tree_leaves(r)[0].ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        r = f(*args)
    np.asarray(jax.tree_util.tree_leaves(r)[0].ravel()[0])
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    args = ap.parse_args()
    from apex_tpu.ops._common import on_chip
    on_tpu = on_chip()
    if not on_tpu:
        args.seq, args.batch = 128, 2

    dt = jnp.bfloat16 if on_tpu else jnp.float32
    mha_fused = SelfMultiheadAttn(args.hidden, args.heads,
                                  impl="fast")   # flash-attention core
    mha_ref = SelfMultiheadAttn(args.hidden, args.heads, impl="default")
    p = mha_fused.init(jax.random.PRNGKey(0), dtype=dt)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (args.seq, args.batch, args.hidden), dt)

    fwd_fused = jax.jit(lambda p, x: mha_fused.apply(p, x))
    fwd_ref = jax.jit(lambda p, x: mha_ref.apply(p, x))

    def loss(fn):
        return jax.jit(jax.grad(
            lambda p, x: fn(p, x).astype(jnp.float32).sum()))

    bwd_fused, bwd_ref = loss(mha_fused.apply), loss(mha_ref.apply)

    res = {
        "metric": "self_mha_latency_ms",
        "config": f"seq{args.seq} b{args.batch} h{args.hidden}",
        "fused_fwd_ms": round(timeit(fwd_fused, p, x), 3),
        "ref_fwd_ms": round(timeit(fwd_ref, p, x), 3),
        "fused_fwdbwd_ms": round(timeit(bwd_fused, p, x), 3),
        "ref_fwdbwd_ms": round(timeit(bwd_ref, p, x), 3),
    }
    res["value"] = res["fused_fwdbwd_ms"]
    res["unit"] = "ms"
    res["vs_baseline"] = round(res["ref_fwdbwd_ms"] /
                               max(res["fused_fwdbwd_ms"], 1e-9), 2)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
