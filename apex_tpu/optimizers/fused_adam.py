"""FusedAdam — single-kernel Adam/AdamW over a flat buffer.

≡ apex.optimizers.FusedAdam (apex/optimizers/fused_adam.py:4,127-305):
the reference partitions params by dtype and issues one
multi_tensor_adam launch per group; here all params live in one flat
fp32 buffer and one Pallas pass applies the whole update.  The
"capturable" CUDA-graph variant (fused_adam.py:199-263) is the *default*
semantics in JAX: lr/step/inv_scale/found_inf are on-device scalars and
the overflow-skip is a masked update inside the kernel — no host sync.

Master weights: when `master_weights=True` (≡ FusedMixedPrecisionLamb /
amp O2 master params), the fp32 flat buffer IS the master copy and
`step()` returns params cast back to their storage dtype.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops import optimizer_kernels as K
from apex_tpu.optimizers import flat as F


class FusedAdamState(NamedTuple):
    step: jnp.ndarray      # i32 scalar
    params: jnp.ndarray    # flat fp32 (master) param buffer
    exp_avg: jnp.ndarray   # flat fp32 m
    exp_avg_sq: jnp.ndarray  # flat fp32 v


class FusedAdam(F.FlatCheckpointMixin):
    """API shape: opt = FusedAdam(lr=...); state = opt.init(params);
    params, state = opt.step(state, grads[, lr=, inv_scale=, found_inf=]).
    """

    _STATE = FusedAdamState

    def __init__(self, lr=1e-3, bias_correction=True, betas=(0.9, 0.999),
                 eps=1e-8, adam_w_mode=True, weight_decay=0.0,
                 amsgrad=False, use_pallas: Optional[bool] = None,
                 master_dtype=jnp.float32, wd_mask=None, lr_scales=None):
        """wd_mask / lr_scales: optional per-leaf pytrees (same structure
        as the params passed to init).  wd_mask leaves (bool or float)
        multiply `weight_decay` per tensor — pass
        get_params_for_weight_decay_optimization(params) for the
        standard no-decay-for-bias/LN groups; lr_scales leaves multiply
        `lr` per tensor.  ≡ the reference's param_groups with distinct
        lr/weight_decay (apex/optimizers/fused_adam.py:156-303), applied
        in ONE kernel pass via in-kernel segment expansion."""
        if amsgrad:
            # ≡ reference raise (apex/optimizers/fused_adam.py:121-122)
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        self.lr = lr
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.use_pallas = use_pallas
        # fp32 is the O2-style master copy; bf16 gives O3-style pure-half
        # state (p+m+v at 6 bytes/param instead of 12) for chips where a
        # billion-param model must fit a single HBM
        self.master_dtype = master_dtype
        self.wd_mask = wd_mask
        self.lr_scales = lr_scales
        self._seg_wd = None     # (n_leaves,) fp32, set by init
        self._seg_lrs = None
        self.spec: Optional[F.FlatSpec] = None

    @property
    def _per_leaf(self) -> bool:
        return self.wd_mask is not None or self.lr_scales is not None

    def init(self, params) -> FusedAdamState:
        # per-leaf hyperparameters need lane-aligned leaf segments so
        # the kernel's row-bounds expansion is exact
        align = K._LANES if self._per_leaf else 1
        self.spec = F.make_spec(params, align=align)
        flat = F.flatten(params, self.master_dtype, pad_to=K.FLAT_TILE,
                         align=align)
        if self._per_leaf:
            self._seg_wd, self._seg_lrs = F.resolve_per_leaf(
                self.wd_mask, self.lr_scales, self.weight_decay, params,
                type(self).__name__)
        # two DISTINCT zero buffers: aliasing one array as both moments
        # makes any later donating jit fail with "donate the same
        # buffer twice" when the state is passed in un-resharded
        return FusedAdamState(step=jnp.zeros((), jnp.int32), params=flat,
                              exp_avg=jnp.zeros_like(flat),
                              exp_avg_sq=jnp.zeros_like(flat))

    def step(self, state: FusedAdamState, grads, lr=None, inv_scale=1.0,
             found_inf=False):
        """One fused step.  Returns (params_pytree, new_state)."""
        if self.spec is None:
            raise RuntimeError("call init(params) before step()")
        # keep the grad buffer in its native (bf16) dtype: the kernel
        # upcasts per block, and halving the flatten+read traffic beats a
        # pre-cast (the unscale/moment math still runs in fp32 in-kernel)
        gdts = {l.dtype for l in jax.tree_util.tree_leaves(grads)}
        gdt = gdts.pop() if len(gdts) == 1 else jnp.float32
        with jax.named_scope("flatten_grads"):
            g_flat = F.flatten(grads, gdt, pad_to=K.FLAT_TILE,
                               align=self.spec.align)
        p_tree, new_state = self.step_flat(state, g_flat, lr=lr,
                                           inv_scale=inv_scale,
                                           found_inf=found_inf)
        return p_tree, new_state

    def step_flat(self, state: FusedAdamState, g_flat, lr=None,
                  inv_scale=1.0, found_inf=False):
        """Step from an already-flat grad buffer (any float dtype, padded
        to state.params length).  This is the zero-copy hot path: a train
        step that differentiates w.r.t. the flat param view gets its grad
        here directly, skipping the per-leaf flatten entirely."""
        with jax.named_scope("adam"):
            found = jnp.asarray(found_inf)
            step_next = state.step + jnp.where(found, 0, 1).astype(jnp.int32)
            if self._per_leaf:
                p, m, v = K.adam_flat_seg(
                    state.params, state.exp_avg, state.exp_avg_sq, g_flat,
                    lr=self.lr if lr is None else lr,
                    step=step_next.astype(jnp.float32),
                    wd_values=self._seg_wd, lr_scale_values=self._seg_lrs,
                    spec=self.spec,
                    beta1=self.beta1, beta2=self.beta2, eps=self.eps,
                    adam_w_mode=self.adam_w_mode,
                    bias_correction=self.bias_correction,
                    inv_scale=inv_scale, found_inf=found,
                    use_pallas_override=self.use_pallas)
            else:
                p, m, v = K.adam_flat(
                    state.params, state.exp_avg, state.exp_avg_sq, g_flat,
                    lr=self.lr if lr is None else lr,
                    step=step_next.astype(jnp.float32),
                    beta1=self.beta1, beta2=self.beta2, eps=self.eps,
                    weight_decay=self.weight_decay,
                    adam_w_mode=self.adam_w_mode,
                    bias_correction=self.bias_correction, inv_scale=inv_scale,
                    found_inf=found, use_pallas_override=self.use_pallas)
            new_state = FusedAdamState(step=step_next, params=p, exp_avg=m,
                                       exp_avg_sq=v)
        return F.unflatten(p, self.spec), new_state

    # checkpoint parity ≡ torch optimizer state_dict: FlatCheckpointMixin
