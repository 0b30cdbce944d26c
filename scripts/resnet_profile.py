"""Per-layer ResNet-50 roofline profiler (VERDICT r4 next-#1).

Times, on the real chip at the bench config (b256, 224x224, bf16):
  * every unique conv shape in RN50 — fwd and fwd+bwd, TFLOP/s and %peak
  * the BN stack cost (pallas welford vs jnp stats A/B)
  * maxpool fwd/bwd
  * full train step decomposition (fwd-only / fwd+bwd / full step)

Per-call host dispatch is not what is measured, so every
measurement loops K iterations INSIDE one jitted program via lax.scan
with a scalar feedback chain (carry + tiny epsilon into the input) that
defeats CSE/hoisting without meaningfully changing the op's traffic.

Usage:  python scripts/resnet_profile.py [conv|bn|pool|step|all]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PEAK_TFLOPS = 197.0  # v5e bf16
HBM_GBPS = 819.0     # v5e

B = 256
K_INNER = 100  # iterations inside one jit call (per-call overhead ~20ms)


def _scan_time(op, out_to_scalar, *args, iters=K_INNER, reps=5):
    """Time `op(*args)` by running `iters` copies inside one jitted scan,
    chaining a tiny scalar from each output into the next input so XLA
    cannot hoist or CSE the body.  Returns seconds per op.

    `reps` calls are issued back-to-back and synced ONCE — dispatch overlaps
    device execution exactly as in bench.py's timing loops."""

    def make(length):
        def many(*a):
            def body(carry, _):
                perturbed = (a[0] + carry.astype(a[0].dtype),) + a[1:]
                out = op(*perturbed)
                return out_to_scalar(out) * 1e-30, None

            c, _ = lax.scan(body, jnp.zeros((), jnp.float32), None,
                            length=length)
            return c
        return jax.jit(many)

    def total(f):
        _ = np.asarray(f(*args))  # compile
        _ = np.asarray(f(*args))  # warm
        best = np.inf
        for _ in range(reps):
            t0 = time.perf_counter()
            _ = np.asarray(f(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    # two-point slope cancels the flat per-call overhead exactly
    lo, hi = max(1, iters // 5), iters
    if hi == lo:
        return total(make(hi)) / hi  # overhead-inclusive single point
    t_lo = total(make(lo))
    t_hi = total(make(hi))
    return max((t_hi - t_lo) / (hi - lo), 1e-9)


def _first_scalar(out):
    # sum over EVERY leaf: a single element would let XLA slice-sink
    # through the op and compute one output pixel (measured: "conv"
    # above peak FLOPs); a full ravel()[0] would force a 1-D relayout.
    # The fused sum costs one read of each output — the roofline floor.
    return sum(jnp.sum(leaf.astype(jnp.float32))
               for leaf in jax.tree.leaves(out))


# (name, H, W, Cin, Cout, k, stride, multiplicity) — every unique conv
# shape in RN50.
RN50_CONVS = [
    ("stem7x7s2", 224, 224, 3, 64, 7, 2, 1),
    ("s1_c1_first", 56, 56, 64, 64, 1, 1, 1),
    ("s1_c1", 56, 56, 256, 64, 1, 1, 2),
    ("s1_c2", 56, 56, 64, 64, 3, 1, 3),
    ("s1_c3", 56, 56, 64, 256, 1, 1, 3),
    ("s1_ds", 56, 56, 64, 256, 1, 1, 1),
    ("s2_c1_first", 56, 56, 256, 128, 1, 1, 1),
    ("s2_c2_s2", 56, 56, 128, 128, 3, 2, 1),
    ("s2_ds_s2", 56, 56, 256, 512, 1, 2, 1),
    ("s2_c1", 28, 28, 512, 128, 1, 1, 3),
    ("s2_c2", 28, 28, 128, 128, 3, 1, 3),
    ("s2_c3", 28, 28, 128, 512, 1, 1, 4),
    ("s3_c1_first", 28, 28, 512, 256, 1, 1, 1),
    ("s3_c2_s2", 28, 28, 256, 256, 3, 2, 1),
    ("s3_ds_s2", 28, 28, 512, 1024, 1, 2, 1),
    ("s3_c1", 14, 14, 1024, 256, 1, 1, 5),
    ("s3_c2", 14, 14, 256, 256, 3, 1, 5),
    ("s3_c3", 14, 14, 256, 1024, 1, 1, 6),
    ("s4_c1_first", 14, 14, 1024, 512, 1, 1, 1),
    ("s4_c2_s2", 14, 14, 512, 512, 3, 2, 1),
    ("s4_ds_s2", 14, 14, 1024, 2048, 1, 2, 1),
    ("s4_c1", 7, 7, 2048, 512, 1, 1, 2),
    ("s4_c2", 7, 7, 512, 512, 3, 1, 2),
    ("s4_c3", 7, 7, 512, 2048, 1, 1, 3),
]


def conv_roofline():
    print(f"{'conv':<14}{'n':>3}{'fb_ms':>9}{'TF/s':>7}{'%pk':>6}"
          f"{'GB/s':>7}{'n*fb_ms':>9}", flush=True)
    tot_fb = 0.0
    rows = []
    for name, h, w, cin, cout, k, s, mult in RN50_CONVS:
        x = jax.random.normal(jax.random.PRNGKey(0), (B, h, w, cin),
                              jnp.bfloat16)
        wgt = jax.random.normal(jax.random.PRNGKey(1), (k, k, cin, cout),
                                jnp.bfloat16) * 0.05

        def conv(x, wgt):
            return lax.conv_general_dilated(
                x, wgt, (s, s), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        ho_, wo_ = -(-h // s), -(-w // s)
        dy = jax.random.normal(jax.random.PRNGKey(2), (B, ho_, wo_, cout),
                               jnp.bfloat16)

        def fb(x, wgt, dy):
            # random cotangent through jax.vjp: grad of .sum() has a
            # constant dy that XLA folds into near-free backward convs
            # (measured 2x "above peak")
            out, vjp = jax.vjp(conv, x, wgt)
            dx, dw = vjp(dy)
            return out, dx, dw

        t_fb = _scan_time(fb, _first_scalar, x, wgt, dy)
        ho, wo = ho_, wo_
        flops = 2 * B * ho * wo * cin * cout * k * k
        # fwd+bwd traffic ~ 3 passes x (in + out) at bf16
        traffic = 3 * 2 * B * (h * w * cin + ho * wo * cout)
        tf_fb = 3 * flops / t_fb / 1e12
        tot_fb += mult * t_fb
        rows.append((name, mult, t_fb))
        print(f"{name:<14}{mult:>3}{t_fb*1e3:>9.3f}{tf_fb:>7.1f}"
              f"{100*tf_fb/PEAK_TFLOPS:>6.1f}{traffic/t_fb/1e9:>7.0f}"
              f"{mult*t_fb*1e3:>9.2f}", flush=True)
    print(f"sum over net: fwd+bwd {tot_fb*1e3:.1f} ms "
          f"({B/tot_fb:.0f} img/s if conv-only)")
    for name, mult, t in sorted(rows, key=lambda r: -r[1] * r[2])[:6]:
        print(f"  top cost: {name} x{mult} = {mult*t*1e3:.2f} ms")


def bn_cost():
    """BN stack cost: pallas welford vs jnp stats, per stage shape."""
    from apex_tpu.parallel.sync_batchnorm import sync_batch_norm

    shapes = [  # (H, W, C, count in RN50)
        (112, 112, 64, 1), (56, 56, 64, 6), (56, 56, 256, 4),
        (28, 28, 128, 7), (28, 28, 512, 5), (14, 14, 256, 11),
        (14, 14, 1024, 7), (7, 7, 512, 4), (7, 7, 2048, 4),
    ]
    import apex_tpu.ops._common as C
    for force in ("1", "0"):
        C._FORCE = force
        tot = 0.0
        for h, w, c, mult in shapes:
            x = jax.random.normal(jax.random.PRNGKey(0), (B, h, w, c),
                                  jnp.bfloat16)
            dy = jax.random.normal(jax.random.PRNGKey(1), (B, h, w, c),
                                   jnp.bfloat16)
            scale = jnp.ones((c,))
            bias = jnp.zeros((c,))
            rm = jnp.zeros((c,))
            rv = jnp.ones((c,))

            def fb(x, scale, bias, dy):
                def f(x, scale, bias):
                    y, _, _ = sync_batch_norm(x, scale, bias, rm, rv,
                                              training=True)
                    return y
                y, vjp = jax.vjp(f, x, scale, bias)
                return (y,) + vjp(dy)

            t = _scan_time(fb, _first_scalar, x, scale, bias, dy)
            tot += mult * t
            gb = (B * h * w * c * 2) / 1e9
            print(f"  pallas={force} bn {h}x{w}x{c:<5} x{mult:>2} "
                  f"{t*1e3:8.3f} ms  ({gb/t:.0f} GB/s per-pass)")
        print(f"pallas={force}: BN stack fwd+bwd total {tot*1e3:.1f} ms")
    C._FORCE = ""


def maxpool_cost():
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 112, 112, 64),
                          jnp.bfloat16)

    def mp(x):
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")

    def fb(x):
        return jax.grad(lambda x: mp(x).astype(jnp.float32).sum())(x)

    t_f = _scan_time(mp, _first_scalar, x)
    t_fb = _scan_time(fb, _first_scalar, x)
    print(f"maxpool fwd {t_f*1e3:.3f} ms  fwd+bwd {t_fb*1e3:.3f} ms")


def step_decomp():
    """Full-model decomposition at the bench config (in-jit scan)."""
    from apex_tpu.models.resnet import ResNet
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

    model = ResNet("resnet50", num_classes=1000, axis_name=None)
    params, mstate = model.init(jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16)
        if p.dtype == jnp.float32 else p, params)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 224, 224, 3),
                          jnp.bfloat16)
    y = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 1000)

    def fwd_inf(x):
        return model.apply(params, mstate, x, training=False)[0]

    def fwd_tr(x):
        return model.apply(params, mstate, x, training=True)[0]

    def loss_fn(p, x):
        logits, nms = model.apply(p, mstate, x, training=True)
        return jnp.mean(softmax_cross_entropy_loss(
            logits.astype(jnp.float32), y)), nms

    def fb(x):
        g, _ = jax.grad(loss_fn, has_aux=True)(params, x)
        return g

    import apex_tpu.ops._common as C
    for force in ("1", "0"):
        C._FORCE = force
        t1 = _scan_time(fwd_inf, _first_scalar, x, iters=5)
        t2 = _scan_time(fwd_tr, _first_scalar, x, iters=5)
        t3 = _scan_time(fb, _first_scalar, x, iters=5)
        print(f"pallas={force}: fwd(eval) {t1*1e3:.2f} ms | fwd(train) "
              f"{t2*1e3:.2f} ms | fwd+bwd {t3*1e3:.2f} ms "
              f"({B/t3:.0f} img/s)")
    C._FORCE = ""


def calibrate():
    """Per-call overhead vs per-iteration cost: time one mid-size conv
    at different inner iteration counts; the slope is the true per-op
    cost."""
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 28, 28, 128),
                          jnp.bfloat16)
    wgt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 128, 128),
                            jnp.bfloat16) * 0.05

    def conv(x, wgt):
        return lax.conv_general_dilated(
            x, wgt, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    for it in (1, 10, 40, 100, 200):
        t = _scan_time(conv, _first_scalar, x, wgt, iters=it)
        print(f"iters={it:>3}: {t*1e3:.3f} ms/op (total {t*it*1e3:.1f})")


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    print(f"backend: {jax.default_backend()}  devices: {jax.devices()}")
    if which == "calib":
        calibrate()
        return
    if which in ("conv", "all"):
        conv_roofline()
    if which in ("bn", "all"):
        bn_cost()
    if which in ("pool", "all"):
        maxpool_cost()
    if which in ("step", "all"):
        step_decomp()


if __name__ == "__main__":
    main()
