"""Decompose the ResNet bench-step tail: model fwd+bwd is ~94 ms but the
bench step is ~118 ms.  Times three variants of the full train step on
the real chip (dispatch-amortized: N calls back-to-back, one sync)."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models.resnet import ResNet
from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
from apex_tpu.optimizers.fused_sgd import FusedSGD
from apex_tpu.parallel import ddp
from apex_tpu.parallel import mesh as M

B = 256


def timeit(step_fn, args, iters=10, warmup=2):
    """step_fn(*args) -> new args tuple (donation-safe state threading)."""
    for _ in range(warmup):
        args = step_fn(*args)
    _ = np.asarray(jax.tree.leaves(args)[0].ravel()[:1])
    t0 = time.perf_counter()
    for _ in range(iters):
        args = step_fn(*args)
    _ = np.asarray(jax.tree.leaves(args)[0].ravel()[:1])
    return (time.perf_counter() - t0) / iters


def main():
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    model = ResNet("resnet50", num_classes=1000, axis_name="dp")
    params, mstate = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 224, 224, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 1000)

    def loss_fn(p, ms, b):
        xb, yb = b
        logits, new_ms = model.apply(p, ms, xb, training=True)
        return jnp.mean(softmax_cross_entropy_loss(
            logits.astype(jnp.float32), yb)), new_ms

    # variant 1: the bench step exactly (amp O1 + ddp.make_train_step)
    amp_state = amp.initialize(opt_level="O1")
    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    state = opt.init(params)
    scaler = amp_state.loss_scalers[0]
    step = ddp.make_train_step(loss_fn, opt, mesh, amp_state=amp_state,
                               batch_spec=(P("dp"), P("dp")),
                               with_state=True)

    def run1(state, scaler, mstate):
        s, sc, ms, _ = step(state, scaler, mstate, (x, y))
        return s, sc, ms

    t = timeit(run1, (state, scaler, mstate))
    print(f"bench step (O1 + scaler + ddp):    {t*1e3:.2f} ms "
          f"({B/t:.0f} img/s)", flush=True)

    # variant 2: same builder, amp O1 but static loss scale (no dynamic
    # scaler state / no check_finite pass)
    amp_state2 = amp.initialize(opt_level="O1", loss_scale=1.0)
    step2 = ddp.make_train_step(loss_fn, opt, mesh, amp_state=amp_state2,
                                batch_spec=(P("dp"), P("dp")),
                                with_state=True)
    scaler2 = amp_state2.loss_scalers[0]
    state_b = opt.init(params)

    def run2(state, scaler, mstate):
        s, sc, ms, _ = step2(state, scaler, mstate, (x, y))
        return s, sc, ms

    t = timeit(run2, (state_b, scaler2, mstate))
    print(f"step (O1, static scale):           {t*1e3:.2f} ms "
          f"({B/t:.0f} img/s)", flush=True)

    # variant 3: minimal — bf16 params, plain jit, no shard_map/amp,
    # fused SGD on the flat buffer
    params16 = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    opt3 = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    state3 = opt3.init(params16)
    x16 = x.astype(jnp.bfloat16)

    def step3c(state, mstate):
        from apex_tpu.optimizers import flat as F
        p = F.unflatten(state.params, opt3.spec)

        def lf(p):
            logits, nms = model.apply(p, mstate, x16, training=True,
                                      axis_name=None)
            loss = jnp.mean(softmax_cross_entropy_loss(
                logits.astype(jnp.float32), y))
            return loss, nms

        grads, nms = jax.grad(lf, has_aux=True)(p)
        new_p, new_state = opt3.step(state, grads)
        return new_state, nms

    jstep3 = jax.jit(step3c, donate_argnums=(0,))
    t = timeit(jstep3, (state3, mstate))
    print(f"minimal (bf16 params, no amp/ddp): {t*1e3:.2f} ms "
          f"({B/t:.0f} img/s)", flush=True)
    M.destroy_model_parallel()


if __name__ == "__main__":
    main()


def scan_variant():
    """K train steps inside ONE jitted scan call: if per-step time drops
    to the profiler's ~94 ms, the gap was host dispatch, not device
    work."""
    M.destroy_model_parallel()
    model = ResNet("resnet50", num_classes=1000, axis_name=None)
    params, mstate = model.init(jax.random.PRNGKey(0))
    params16 = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    state = opt.init(params16)
    x16 = jax.random.normal(jax.random.PRNGKey(1), (B, 224, 224, 3),
                            jnp.bfloat16)
    y = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 1000)
    K = 10

    def one(carry, _):
        state, mstate = carry
        from apex_tpu.optimizers import flat as F
        p = F.unflatten(state.params, opt.spec)

        def lf(p):
            logits, nms = model.apply(p, mstate, x16, training=True)
            loss = jnp.mean(softmax_cross_entropy_loss(
                logits.astype(jnp.float32), y))
            return loss, nms

        grads, nms = jax.grad(lf, has_aux=True)(p)
        _, new_state = opt.step(state, grads)
        return (new_state, nms), None

    def many(state, mstate):
        (s, ms), _ = jax.lax.scan(one, (state, mstate), None, length=K)
        return s, ms

    jmany = jax.jit(many, donate_argnums=(0, 1))

    def run(state, mstate):
        return jmany(state, mstate)

    t = timeit(run, (state, mstate), iters=3, warmup=1)
    print(f"scan x{K} minimal:                  {t/K*1e3:.2f} ms/step "
          f"({B/(t/K):.0f} img/s)", flush=True)


if __name__ == "__main__":
    pass
