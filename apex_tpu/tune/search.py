"""Offline kernel-config search — the timing half of apex_tpu.tune.

OFFLINE ONLY: every candidate is compiled and timed wall-clock as its
own jitted program (never inside a training step — a tuner that times
inside jit would perturb exactly what it measures and sync the host).
Winners are written to the persistent cache via cache.record; the
kernels pick them up at their next trace through tune.tuned().

CLI: ``python scripts/gpt_anatomy.py tune [targets...] [--check]``.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from apex_tpu.tune import cache


def _time_fn(fn, args, iters=10, warmup=2, reps=2) -> float:
    """Best-of-reps mean seconds per call, fully synced."""
    import jax

    out = None
    for _ in range(warmup):
        out = fn(*args)
    _ = np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[0])
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        _ = np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[0])
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


@contextlib.contextmanager
def forced(op: str, attrs: Dict[str, Any], config: Dict[str, Any]):
    """Temporarily pin (op, attrs) -> config in the IN-MEMORY cache so a
    kernel with no explicit config knob can be timed at a candidate.
    Re-trace (fresh jit) inside the context — lookups happen at trace
    time."""
    key = cache.make_key(op, attrs)
    mem = cache._ensure_loaded()
    missing = object()
    old = mem.get(key, missing)
    mem[key] = {"config": dict(config)}
    try:
        yield
    finally:
        if old is missing:
            mem.pop(key, None)
        else:
            mem[key] = old


# ------------------------------ flash attention -----------------------------

def flash_candidates(sq: int, sk: int,
                     max_score_elems: int = 512 * 1024
                     ) -> List[Dict[str, int]]:
    """Candidate (block_q, block_k) grid: blocks divide the sequence
    and the fp32 score block (bk·bq) stays within ~2 MB of VMEM."""
    blocks = (128, 256, 512, 1024)
    return [{"block_q": bq, "block_k": bk}
            for bq in blocks if sq % bq == 0
            for bk in blocks if sk % bk == 0
            and bq * bk <= max_score_elems]


def flash_attrs(b, h, s, d, dtype, causal, bias="none", seg=False):
    """Self-attention (sq == sk == s) flash key attrs — delegates to
    the shared definition in apex_tpu.tune.flash_attrs."""
    from apex_tpu.tune import flash_attrs as _shared

    return _shared(b, h, s, s, d, dtype, causal, bias=bias, seg=seg)


def tune_flash(b: int, h: int, s: int, d: int, *, dtype=None,
               causal: bool = True, seg: bool = False,
               iters: int = 10, write: bool = True,
               use_pallas_override: Optional[bool] = None,
               verbose: bool = False
               ) -> Tuple[Dict[str, int], List[Tuple[Dict, float]]]:
    """Sweep flash fwd+bwd configs at one (shape, dtype) point; returns
    (best_config, [(config, seconds), ...]) and records the winner."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.flash_attention import flash_attention

    dtype = dtype or jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), dtype) for kk in ks)
    seg_ids = (jnp.zeros((b, s), jnp.int32) if seg else None)

    results = []
    for cand in flash_candidates(s, s):
        def fb(q, k, v, cand=cand):
            def f(q, k, v):
                return flash_attention(
                    q, k, v, causal=causal, segment_ids=seg_ids,
                    block_q=cand["block_q"], block_k=cand["block_k"],
                    use_pallas_override=use_pallas_override)
            out, vjp = jax.vjp(f, q, k, v)
            return (out,) + vjp(out)

        try:
            # deliberate jit-per-candidate: every candidate IS a
            # different program; the sweep pays one compile each
            t = _time_fn(jax.jit(fb), (q, k, v), iters=iters)  # lint: disable=HS405
        except Exception as e:  # candidate may not compile on this chip
            if verbose:
                print(f"  flash {cand}: FAIL {repr(e)[:80]}", flush=True)
            continue
        results.append((cand, t))
        if verbose:
            print(f"  flash {cand}: {t*1e3:.3f} ms", flush=True)
    if not results:
        raise RuntimeError("no flash candidate compiled")
    results.sort(key=lambda r: r[1])
    best, best_t = results[0]
    attrs = flash_attrs(b, h, s, d, dtype, causal, seg=seg)
    if write:
        cache.record("flash_sdpa", attrs, best,
                     meta={"ms": round(best_t * 1e3, 4),
                           "swept": len(results)})
    return best, results


# --------------------------- row-blocked kernels ----------------------------

def _row_block_candidates(rows: int) -> List[int]:
    from apex_tpu.tune import pow2_bucket

    cap = pow2_bucket(rows)
    return [c for c in (64, 128, 256, 512, 1024) if c <= max(cap, 64)]


def tune_row_block(op: str, rows: int, hidden: int, *, dtype=None,
                   iters: int = 10, write: bool = True,
                   use_pallas_override: Optional[bool] = None):
    """Sweep the row-block of the softmax / layer-norm kernels (op in
    {"softmax_fwd", "softmax_bwd", "layer_norm_fwd", "layer_norm_bwd"}).
    fwd and bwd share one fwd+bwd timing sweep per family — the two
    entries are recorded with the same winning block."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.tune import pow2_bucket

    dtype = dtype or jnp.bfloat16
    family = op.rsplit("_", 1)[0]
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, hidden), dtype)
    attrs_f = dict(rows=pow2_bucket(rows), hidden=hidden)

    def fb_factory():
        if family == "softmax":
            from apex_tpu.ops.softmax import scaled_softmax

            def f(x):
                return scaled_softmax(
                    x, 1.0, use_pallas_override=use_pallas_override)
        else:
            from apex_tpu.ops.layer_norm import fused_layer_norm

            w = jnp.ones((hidden,), jnp.float32)
            bb = jnp.zeros((hidden,), jnp.float32)

            def f(x):
                return fused_layer_norm(
                    x, w, bb,
                    use_pallas_override=(True if use_pallas_override
                                         is None else
                                         use_pallas_override))

        def fb(x):
            out, vjp = jax.vjp(f, x)
            return (out,) + vjp(out)
        return fb

    results = []
    for blk in _row_block_candidates(rows):
        cfg = {"block_rows": blk}
        with forced(family + "_fwd", attrs_f, cfg), \
                forced(family + "_bwd", attrs_f, cfg):
            try:
                # deliberate jit-per-candidate sweep (see tune_flash)
                t = _time_fn(jax.jit(fb_factory()), (x,), iters=iters)  # lint: disable=HS405
            except Exception:
                continue
        results.append((cfg, t))
    if not results:
        raise RuntimeError(f"no {family} row-block candidate compiled")
    results.sort(key=lambda r: r[1])
    best, best_t = results[0]
    if write:
        for suffix in ("_fwd", "_bwd"):
            cache.record(family + suffix, attrs_f, best,
                         meta={"ms": round(best_t * 1e3, 4)})
    return best, results


# ------------------------------ flat optimizers -----------------------------

def tune_opt_flat(n: int, *, kernel: str = "adam", iters: int = 10,
                  write: bool = True,
                  use_pallas_override: Optional[bool] = None):
    """Sweep rows-per-block of the flat Adam kernel at `n` params."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import optimizer_kernels as K
    from apex_tpu.tune import pow2_bucket

    n = -(-n // K.FLAT_TILE) * K.FLAT_TILE
    rows = n // K._LANES
    attrs = dict(kernel=kernel, rows=pow2_bucket(rows))
    p = jnp.zeros((n,), jnp.float32)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    g = jnp.full((n,), 1e-3, jnp.bfloat16)

    results = []
    for blk in (128, 256, 512):
        if rows % blk:
            continue
        with forced("opt_flat", attrs, {"block_rows": blk}):
            # deliberate jit-per-candidate sweep (see tune_flash)
            step = jax.jit(functools.partial(  # lint: disable=HS405
                K.adam_flat, lr=1e-3, step=10,
                use_pallas_override=use_pallas_override))
            try:
                t = _time_fn(step, (p, m, v, g), iters=iters)
            except Exception:
                continue
        results.append(({"block_rows": blk}, t))
    if not results:
        raise RuntimeError("no opt_flat candidate compiled")
    results.sort(key=lambda r: r[1])
    best, best_t = results[0]
    if write:
        cache.record("opt_flat", attrs, best,
                     meta={"ms": round(best_t * 1e3, 4)})
    return best, results
