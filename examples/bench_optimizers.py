"""FusedAdam step-time micro-benchmark at large parameter counts.

TPU counterpart of the driver metric "FusedAdam step ms @ 1B params"
(BASELINE.json; the reference's tests/L0/run_optimizers are
correctness-only).  One fused Pallas Adam launch over a single flat
donated buffer — the design that replaces amp_C.multi_tensor_adam's
chunked ≤110-tensor launches (csrc/multi_tensor_apply.cuh:15-16).

Run:  python examples/bench_optimizers.py [n_params ...]
Prints one JSON line per size.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def bench_adam(n: int, param_dtype=jnp.float32, iters: int = 20,
               warmup: int = 3) -> dict:
    from apex_tpu.ops import optimizer_kernels as K

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()

    # tile-aligned, as FusedAdam.init allocates (flatten(pad_to=FLAT_TILE)):
    # unaligned buffers force a pad copy that breaks in-place aliasing
    n = -(-n // K.FLAT_TILE) * K.FLAT_TILE
    p = jnp.zeros((n,), param_dtype)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    g = jnp.full((n,), 1e-3, jnp.bfloat16 if on_tpu else param_dtype)

    def _step(p, m, v, g):
        return K.adam_flat(p, m, v, g, lr=1e-3, step=10,
                           weight_decay=0.01,
                           use_pallas_override=on_tpu or None)

    # donate: the aliased Pallas call updates p/m/v in place
    step = jax.jit(_step, donate_argnums=(0, 1, 2))

    for _ in range(warmup):
        p, m, v = step(p, m, v, g)
    np.asarray(p[:1])  # sync
    t0 = time.perf_counter()
    for _ in range(iters):
        p, m, v = step(p, m, v, g)
    np.asarray(p[:1])
    ms = (time.perf_counter() - t0) / iters * 1e3
    # HBM bytes touched: p read+write, m/v read+write (fp32), one bf16 g read
    itemsize = jnp.dtype(param_dtype).itemsize
    bytes_moved = n * (2 * itemsize + 4 * 4 + 2) if on_tpu else None
    return {
        "metric": f"fused_adam_step_ms_at_{n/1e9:.2g}B_params",
        "value": round(ms, 3),
        "unit": "ms",
        "dtype": str(jnp.dtype(param_dtype)),
        "gb_per_s": round(bytes_moved / (ms / 1e3) / 1e9, 1)
        if bytes_moved else None,
        "vs_baseline": 1.0,
    }


def main():
    sizes = [int(float(a)) for a in sys.argv[1:]] or [2**27, 10**9]
    if jax.default_backend() == "cpu":
        sizes = [2**20]
    for n in sizes:
        dt = jnp.float32
        try:
            print(json.dumps(bench_adam(n, dt)))
        except Exception as e:  # OOM at 1B fp32 on 16GB: retry bf16 params
            print(f"# {n} fp32 failed ({type(e).__name__}); retrying bf16",
                  file=sys.stderr)
            print(json.dumps(bench_adam(n, jnp.bfloat16)))


if __name__ == "__main__":
    main()
