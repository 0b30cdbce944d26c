"""Fused optimizer kernels over flat 1-D parameter buffers — Pallas.

≡ the reference's `amp_C` extension (csrc/amp_C_frontend.cpp:175-204):
multi_tensor_{adam,sgd,adagrad,novograd,lamb,l2norm,scale,axpby} built on
the chunked multi_tensor_apply launcher (csrc/multi_tensor_apply.cuh:19-100).
The TPU re-design replaces "hundreds of tensors, chunked kernel launches"
with ONE flat fp32 buffer per state (see optimizers/flat.py for the
pytree<->buffer mapping ≡ apex_C.flatten/unflatten): a single Pallas
pass reads grad and state, applies decay/moments/bias-correction/update,
and writes params+state in place (input_output_aliases ≡ in-place CUDA
functors).  Grad unscaling and the overflow-skip are fused into the same
pass (≡ the capturable CUDA-graph Adam, apex/optimizers/fused_adam.py:199-263:
`inv_scale` multiply + `found_inf` masked update, no host sync).

Per-tensor reductions (LAMB trust ratios, NovoGrad per-tensor norms) are
computed as XLA segmented reductions over the flat buffer and passed in
as per-element vectors — the analogue of the reference's two-phase
l2norm→lamb launch pair (apex/optimizers/fused_lamb.py:124-199).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.monitor.compile.startup import kernel_span
from apex_tpu.ops._common import pallas_interpret, use_pallas

_LANES = 128
_BLOCK_ROWS = 512  # (512, 128) fp32 tile = 256 KiB per operand

# Flat buffers created at optimizer init should be padded to this length
# multiple (optimizers/flat.py flatten(pad_to=...)); _to2d is then a free
# bitcast and the kernels run fully in place via input_output_aliases.
FLAT_TILE = _BLOCK_ROWS * _LANES


def _to2d(flat):
    n = flat.shape[0]
    pad = (-n) % (_BLOCK_ROWS * _LANES)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _LANES), n


def _block_rows(rows: int, kernel: str) -> int:
    """Rows-per-block for a flat kernel's grid: the autotuned value for
    (kernel, pow2-bucketed rows) when it divides the (FLAT_TILE-padded)
    row count, else the swept default _BLOCK_ROWS.  Trace-time lookup
    only (apex_tpu.tune) — an empty cache is byte-identical to the
    constant."""
    from apex_tpu import tune

    cfg = tune.tuned("opt_flat", dict(kernel=kernel,
                                      rows=tune.pow2_bucket(rows)))
    if cfg:
        br = cfg.get("block_rows")
        if isinstance(br, int) and 8 <= br <= 4096 and rows % br == 0:
            return br
    return _BLOCK_ROWS


def _from2d(x2, n):
    return x2.reshape(-1)[:n]


# ------------------------------- Adam ---------------------------------------

def _adam_kernel(p_ref, m_ref, v_ref, g_ref, sc_ref,
                 p_out, m_out, v_out, *,
                 eps, weight_decay, adam_w_mode):
    """sc_ref rows: [lr_eff, inv_scale, b1e, c1, b2e, c2, rbc1, rbc2,
    found].

    The overflow-skip and bias correction are FOLDED INTO THE SCALARS on
    the host (adam_flat): found_inf sets lr_eff=0, b*e=1, c*=0 and the
    single g select below zeroes the (inf/nan) grad stream, so the
    elementwise pass needs one select instead of three and the 1/bc
    divides become rbc multiplies — the VPU (not HBM) is the bound for
    bf16 state, so per-element op count is what this kernel optimizes."""
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lr_eff = sc_ref[0, 0]
    inv_scale = sc_ref[1, 0]
    b1e, c1 = sc_ref[2, 0], sc_ref[3, 0]
    b2e, c2 = sc_ref[4, 0], sc_ref[5, 0]
    rbc1, rbc2 = sc_ref[6, 0], sc_ref[7, 0]
    # the one per-element select: inf/nan grads would otherwise poison
    # m/v through 0*inf=nan even with c1=c2=0
    g = jnp.where(sc_ref[8, 0] > 0.5, 0.0, g * inv_scale)
    if not adam_w_mode and weight_decay != 0.0:
        g = g + weight_decay * p  # L2 mode ≡ ADAM_MODE_1 (multi_tensor_adam.cu)
    m_new = b1e * m + c1 * g
    v_new = b2e * v + c2 * (g * g)
    update = (m_new * rbc1) / (jnp.sqrt(v_new * rbc2) + eps)
    if adam_w_mode and weight_decay != 0.0:
        update = update + weight_decay * p  # AdamW ≡ ADAM_MODE_0
    p_out[...] = (p - lr_eff * update).astype(p_out.dtype)
    m_out[...] = m_new.astype(m_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)


def _adam_fold_scalars(lr, step, beta1, beta2, bias_correction,
                       inv_scale, found_inf):
    """The ONE definition of the Adam folded-scalar rows (shared by the
    uniform and per-tensor-seg variants, which must stay numerically
    identical).  clamp: at step 0 (reachable only when found_inf skips
    the very first update, so m=v=0) bc would be 0 and 1/bc inf —
    inf*0=nan would poison the select-free kernel."""
    step = jnp.asarray(step, jnp.float32)
    bc1 = jnp.maximum(1.0 - jnp.power(jnp.float32(beta1), step), 1e-20)
    bc2 = jnp.maximum(1.0 - jnp.power(jnp.float32(beta2), step), 1e-20)
    one = jnp.float32(1.0)
    keep = jnp.asarray(found_inf).astype(jnp.bool_)
    # fold overflow-skip + bias correction into broadcast scalars: the
    # kernel then runs select-free and divide-free (one vector divide
    # left) — see _adam_kernel
    return jnp.stack([
        jnp.where(keep, 0.0, jnp.asarray(lr, jnp.float32)),   # lr_eff
        jnp.asarray(inv_scale, jnp.float32),
        jnp.where(keep, one, jnp.float32(beta1)),             # b1e
        jnp.where(keep, 0.0, 1.0 - jnp.float32(beta1)),       # c1
        jnp.where(keep, one, jnp.float32(beta2)),             # b2e
        jnp.where(keep, 0.0, 1.0 - jnp.float32(beta2)),       # c2
        one / bc1 if bias_correction else one,                # rbc1
        one / bc2 if bias_correction else one,                # rbc2
        keep.astype(jnp.float32),                             # found
    ]).reshape(9, 1)


def adam_flat(p, m, v, g, lr, step, *, beta1=0.9, beta2=0.999, eps=1e-8,
              weight_decay=0.0, adam_w_mode=True, bias_correction=True,
              inv_scale=1.0, found_inf=False, use_pallas_override=None):
    """One fused Adam/AdamW step on flat buffers.

    ≡ amp_C.multi_tensor_adam / multi_tensor_adam_capturable
    (csrc/multi_tensor_adam.cu).  `step` may be traced (on-device step
    count, ≡ capturable mode's GPU-side `step` tensor).
    Returns (p, m, v) new buffers (donate inputs under jit).
    """
    scalars = _adam_fold_scalars(lr, step, beta1, beta2, bias_correction,
                                 inv_scale, found_inf)
    if not use_pallas(use_pallas_override):
        return _adam_reference(p, m, v, g, scalars, eps,
                               weight_decay, adam_w_mode)
    kernel = functools.partial(
        _adam_kernel, eps=eps,
        weight_decay=weight_decay, adam_w_mode=adam_w_mode)
    p2, np_ = _to2d(p)
    m2, _ = _to2d(m)
    v2, _ = _to2d(v)
    g2, _ = _to2d(g)
    rows = p2.shape[0]
    R = _block_rows(rows, "adam")
    grid = rows // R
    spec = pl.BlockSpec((R, _LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((9, 1), lambda i: (0, 0))
    with kernel_span("adam_flat"):
        pn, mn, vn = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[spec, spec, spec, spec, sspec],
            out_specs=[spec, spec, spec],
            out_shape=[jax.ShapeDtypeStruct(p2.shape, p2.dtype),
                       jax.ShapeDtypeStruct(m2.shape, m2.dtype),
                       jax.ShapeDtypeStruct(v2.shape, v2.dtype)],
            input_output_aliases={0: 0, 1: 1, 2: 2},
            interpret=pallas_interpret(),
            name="adam_flat",
        )(p2, m2, v2, g2, scalars)
    return _from2d(pn, np_), _from2d(mn, np_), _from2d(vn, np_)


def _adam_reference(p, m, v, g, scalars, eps, weight_decay, adam_w_mode):
    """Same folded-scalar contract as _adam_kernel (the CPU oracle)."""
    (lr_eff, inv_scale, b1e, c1, b2e, c2, rbc1, rbc2, found) = [
        scalars[i, 0] for i in range(9)]
    g = jnp.where(found > 0.5, 0.0, g.astype(jnp.float32) * inv_scale)
    p32 = p.astype(jnp.float32)
    if not adam_w_mode and weight_decay:
        g = g + weight_decay * p32
    m32 = m.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    m_new = b1e * m32 + c1 * g
    v_new = b2e * v32 + c2 * (g * g)
    update = (m_new * rbc1) / (jnp.sqrt(v_new * rbc2) + eps)
    if adam_w_mode and weight_decay:
        update = update + weight_decay * p32
    p_new = p32 - lr_eff * update
    return (p_new.astype(p.dtype), m_new.astype(m.dtype),
            v_new.astype(v.dtype))


def _adam_seg_kernel(p_ref, m_ref, v_ref, g_ref, sc_ref, lo_ref, hi_ref,
                     vals_ref, off_ref, p_out, m_out, v_out, *,
                     eps, adam_w_mode, npad, R):
    """_adam_kernel with PER-TENSOR weight decay and lr scale: vals_ref
    row 0 holds each tensor's weight decay, row 1 its lr multiplier;
    the per-row pair is rebuilt per block from the static segment row
    bounds via one one-hot matmul (the lamb_phase2_seg trick) — the
    (total,) per-element vectors never exist in HBM.

    ≡ the reference's param_groups loop (apex/optimizers/fused_adam.py:
    156-303), which launches multi_tensor_adam once per group with that
    group's lr/weight_decay — here one pass covers every group.
    Padding rows fall outside every bound → wd=0 AND lr scale 0, so the
    zero-filled tails never move."""
    i = pl.program_id(0)
    oh = _block_onehot(lo_ref, hi_ref, off_ref, i, R, npad)
    # one select-matmul yields both per-row values; HIGHEST keeps the
    # fp32 hyperparameters exact (default MXU path rounds to bf16)
    wl = jax.lax.dot_general(oh, vals_ref[0:2, :],
                             (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST)
    wd_row, lrs_row = wl[:, 0:1], wl[:, 1:2]
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lr_eff = sc_ref[0, 0]
    inv_scale = sc_ref[1, 0]
    b1e, c1 = sc_ref[2, 0], sc_ref[3, 0]
    b2e, c2 = sc_ref[4, 0], sc_ref[5, 0]
    rbc1, rbc2 = sc_ref[6, 0], sc_ref[7, 0]
    g = jnp.where(sc_ref[8, 0] > 0.5, 0.0, g * inv_scale)
    if not adam_w_mode:
        g = g + wd_row * p
    m_new = b1e * m + c1 * g
    v_new = b2e * v + c2 * (g * g)
    update = (m_new * rbc1) / (jnp.sqrt(v_new * rbc2) + eps)
    if adam_w_mode:
        update = update + wd_row * p
    p_out[...] = (p - lr_eff * lrs_row * update).astype(p_out.dtype)
    m_out[...] = m_new.astype(m_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)


def _seg_vals2(wd_values, lr_scale_values, npad):
    n_seg = wd_values.shape[0]
    vals = jnp.zeros((8, npad), jnp.float32)
    vals = vals.at[0, :n_seg].set(wd_values.astype(jnp.float32))
    vals = vals.at[1, :n_seg].set(lr_scale_values.astype(jnp.float32))
    return vals


def adam_flat_seg(p, m, v, g, lr, step, *, wd_values, lr_scale_values,
                  spec, row_offset=0, padded_total=None,
                  beta1=0.9, beta2=0.999, eps=1e-8, adam_w_mode=True,
                  bias_correction=True, inv_scale=1.0, found_inf=False,
                  use_pallas_override=None):
    """adam_flat with per-tensor (weight_decay, lr_scale) vectors — the
    consumer of get_params_for_weight_decay_optimization's mask.  `spec`
    must be lane-aligned (FlatSpec(align=128)); `row_offset` is p's
    global starting row for ZeRO shards (may be traced; `padded_total`
    is then required for the jnp fallback's segment map)."""
    scalars = _adam_fold_scalars(lr, step, beta1, beta2, bias_correction,
                                 inv_scale, found_inf)
    wd_values = jnp.asarray(wd_values, jnp.float32)
    lr_scale_values = jnp.asarray(lr_scale_values, jnp.float32)
    n_seg = wd_values.shape[0]
    npad = _seg_pad(n_seg)
    if not (use_pallas(use_pallas_override) and n_seg + 1 < _SEG_CAP
            and p.shape[0] % FLAT_TILE == 0):
        rows = p.shape[0] // _LANES
        total = padded_total if padded_total is not None else p.shape[0]
        rank = jnp.asarray(row_offset, jnp.int32) // rows
        seg = shard_segment_ids(spec, rank, rows, total)
        wd_elem = expand_per_tensor_shard(wd_values, seg)
        lrs_elem = expand_per_tensor_shard(lr_scale_values, seg)
        return _adam_seg_reference(p, m, v, g, scalars, eps, adam_w_mode,
                                   wd_elem, lrs_elem)
    p2, np_ = _to2d(p)
    m2, _ = _to2d(m)
    v2, _ = _to2d(v)
    g2, _ = _to2d(g)
    R = _block_rows(p2.shape[0], "adam_seg")
    grid = p2.shape[0] // R
    lo, hi = _seg_row_bounds(spec, npad)
    vals = _seg_vals2(wd_values, lr_scale_values, npad)
    off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
    bspec = pl.BlockSpec((8, npad), lambda i: (0, 0))
    spec_b = pl.BlockSpec((R, _LANES), lambda i: (i, 0))
    with kernel_span("adam_flat_seg"):
        pn, mn, vn = pl.pallas_call(
            functools.partial(_adam_seg_kernel, eps=eps,
                              adam_w_mode=adam_w_mode, npad=npad, R=R),
            grid=(grid,),
            in_specs=[spec_b, spec_b, spec_b, spec_b,
                      pl.BlockSpec((9, 1), lambda i: (0, 0)),
                      bspec, bspec, bspec,
                      pl.BlockSpec((1, 1), lambda i: (0, 0))],
            out_specs=[spec_b, spec_b, spec_b],
            out_shape=[jax.ShapeDtypeStruct(p2.shape, p2.dtype),
                       jax.ShapeDtypeStruct(m2.shape, m2.dtype),
                       jax.ShapeDtypeStruct(v2.shape, v2.dtype)],
            input_output_aliases={0: 0, 1: 1, 2: 2},
            interpret=pallas_interpret(),
            name="adam_flat_seg",
        )(p2, m2, v2, g2, scalars, lo, hi, vals, off)
    return _from2d(pn, np_), _from2d(mn, np_), _from2d(vn, np_)


def _adam_seg_reference(p, m, v, g, scalars, eps, adam_w_mode, wd_elem,
                        lrs_elem):
    """Per-element-vector oracle with the same folded-scalar contract."""
    (lr_eff, inv_scale, b1e, c1, b2e, c2, rbc1, rbc2, found) = [
        scalars[i, 0] for i in range(9)]
    g = jnp.where(found > 0.5, 0.0, g.astype(jnp.float32) * inv_scale)
    p32 = p.astype(jnp.float32)
    if not adam_w_mode:
        g = g + wd_elem * p32
    m_new = b1e * m.astype(jnp.float32) + c1 * g
    v_new = b2e * v.astype(jnp.float32) + c2 * (g * g)
    update = (m_new * rbc1) / (jnp.sqrt(v_new * rbc2) + eps)
    if adam_w_mode:
        update = update + wd_elem * p32
    p_new = p32 - lr_eff * lrs_elem * update
    return (p_new.astype(p.dtype), m_new.astype(m.dtype),
            v_new.astype(v.dtype))


# ------------------------------- SGD ----------------------------------------

def _sgd_kernel(p_ref, b_ref, g_ref, sc_ref, p_out, b_out, *,
                momentum, dampening, nesterov, weight_decay,
                wd_after_momentum, first_run):
    """sc rows: [lr, inv_scale, found_inf, first].  `first` selects the
    buf:=g initialization (torch's buf-is-None branch) IN-kernel so one
    aliased pass covers step 0 and steady state — a host-side where on
    the buffer would materialize a copy and break in-place aliasing.
    `first_run=True` forces the init branch statically."""
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    lr = sc_ref[0, 0]
    inv_scale = sc_ref[1, 0]
    found_inf = sc_ref[2, 0]
    first = sc_ref[3, 0] > 0.5
    g = g * inv_scale
    if weight_decay != 0.0 and not wd_after_momentum:
        g = g + weight_decay * p
    if momentum != 0.0:
        if first_run:
            b_new = g
        else:
            b_steady = momentum * b + (1.0 - dampening) * g
            b_new = jnp.where(first, g, b_steady)
        upd = g + momentum * b_new if nesterov else b_new
    else:
        b_new = b
        upd = g
    if weight_decay != 0.0 and wd_after_momentum:
        upd = upd + weight_decay * p
    p_new = p - lr * upd
    keep = found_inf > 0.5
    p_out[...] = jnp.where(keep, p, p_new).astype(p_out.dtype)
    b_out[...] = jnp.where(keep, b, b_new).astype(b_out.dtype)


def sgd_flat(p, buf, g, lr, *, momentum=0.0, dampening=0.0, nesterov=False,
             weight_decay=0.0, wd_after_momentum=False, first_run=False,
             first=False, inv_scale=1.0, found_inf=False,
             use_pallas_override=None):
    """≡ amp_C.multi_tensor_sgd (csrc/multi_tensor_sgd_kernel.cu).
    Returns (p, momentum_buffer).  `first` (traced bool) selects the
    buf:=g first-step branch in-kernel; `first_run` is its static form."""
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(inv_scale, jnp.float32),
        jnp.asarray(found_inf, jnp.float32),
        jnp.asarray(first, jnp.float32),
    ]).reshape(4, 1)
    if not use_pallas(use_pallas_override):
        # jnp fallback mirrors the kernel exactly
        g32 = g.astype(jnp.float32) * scalars[1, 0]
        p32 = p.astype(jnp.float32)
        if weight_decay and not wd_after_momentum:
            g32 = g32 + weight_decay * p32
        if momentum != 0.0:
            if first_run:
                b_new = g32
            else:
                b_new = jnp.where(scalars[3, 0] > 0.5, g32,
                                  momentum * buf.astype(jnp.float32)
                                  + (1 - dampening) * g32)
            upd = g32 + momentum * b_new if nesterov else b_new
        else:
            b_new, upd = buf, g32
        if weight_decay and wd_after_momentum:
            upd = upd + weight_decay * p32
        p_new = p32 - scalars[0, 0] * upd
        keep = scalars[2, 0] > 0.5
        b32 = buf.astype(jnp.float32)
        b_new = b_new.astype(jnp.float32)
        return (jnp.where(keep, p32, p_new).astype(p.dtype),
                jnp.where(keep, b32, b_new).astype(buf.dtype))
    kernel = functools.partial(
        _sgd_kernel, momentum=momentum, dampening=dampening,
        nesterov=nesterov, weight_decay=weight_decay,
        wd_after_momentum=wd_after_momentum, first_run=first_run)
    p2, n = _to2d(p)
    b2, _ = _to2d(buf)
    g2, _ = _to2d(g)
    R = _block_rows(p2.shape[0], "sgd")
    grid = p2.shape[0] // R
    spec = pl.BlockSpec((R, _LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((4, 1), lambda i: (0, 0))
    with kernel_span("sgd_flat"):
        pn, bn = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[spec, spec, spec, sspec],
            out_specs=[spec, spec],
            out_shape=[jax.ShapeDtypeStruct(p2.shape, p2.dtype),
                       jax.ShapeDtypeStruct(b2.shape, b2.dtype)],
            input_output_aliases={0: 0, 1: 1},
            interpret=pallas_interpret(),
            name="sgd_flat",
        )(p2, b2, g2, scalars)
    return _from2d(pn, n), _from2d(bn, n)


# ----------------------------- Adagrad --------------------------------------

def _adagrad_kernel(p_ref, h_ref, g_ref, sc_ref, p_out, h_out, *,
                    eps, weight_decay, adagrad_w_mode):
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    h = h_ref[...]
    lr = sc_ref[0, 0]
    if not adagrad_w_mode and weight_decay != 0.0:
        g = g + weight_decay * p
    h_new = h + g * g
    upd = g / (jnp.sqrt(h_new) + eps)
    if adagrad_w_mode and weight_decay != 0.0:
        upd = upd + weight_decay * p
    p_out[...] = (p - lr * upd).astype(p_out.dtype)
    h_out[...] = h_new


def adagrad_flat(p, h, g, lr, *, eps=1e-10, weight_decay=0.0,
                 adagrad_w_mode=False, use_pallas_override=None):
    """≡ amp_C.multi_tensor_adagrad (csrc/multi_tensor_adagrad.cu)."""
    scalars = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    if not use_pallas(use_pallas_override):
        g32 = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        if not adagrad_w_mode and weight_decay:
            g32 = g32 + weight_decay * p32
        h_new = h + g32 * g32
        upd = g32 / (jnp.sqrt(h_new) + eps)
        if adagrad_w_mode and weight_decay:
            upd = upd + weight_decay * p32
        return (p32 - scalars[0, 0] * upd).astype(p.dtype), h_new
    kernel = functools.partial(_adagrad_kernel, eps=eps,
                               weight_decay=weight_decay,
                               adagrad_w_mode=adagrad_w_mode)
    p2, n = _to2d(p)
    h2, _ = _to2d(h)
    g2, _ = _to2d(g)
    R = _block_rows(p2.shape[0], "adagrad")
    grid = p2.shape[0] // R
    spec = pl.BlockSpec((R, _LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    with kernel_span("adagrad_flat"):
        pn, hn = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[spec, spec, spec, sspec],
            out_specs=[spec, spec],
            out_shape=[jax.ShapeDtypeStruct(p2.shape, p2.dtype),
                       jax.ShapeDtypeStruct(h2.shape, jnp.float32)],
            input_output_aliases={0: 0, 1: 1},
            interpret=pallas_interpret(),
            name="adagrad_flat",
        )(p2, h2, g2, scalars)
    return _from2d(pn, n), _from2d(hn, n)


# ------------------------- LAMB (two-phase) ---------------------------------

def _lamb_phase1_kernel(m_ref, v_ref, g_ref, p_ref, sc_ref,
                        m_out, v_out, u_out, *, eps, weight_decay):
    """Phase 1 ≡ amp_C.multi_tensor_lamb_stage1 / lamb stage computing the
    raw update u = mhat/(sqrt(vhat)+eps) + wd*p with global-grad-norm
    clipping fused.  sc rows: [g_scale, b1e, c1, b2e, c2, rbc1, rbc2,
    found] — overflow skip + bias correction folded into scalars like
    _adam_kernel (one g select; reciprocal-multiply bias correction)."""
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    g = jnp.where(sc_ref[7, 0] > 0.5, 0.0, g * sc_ref[0, 0])
    m_new = sc_ref[1, 0] * m_ref[...] + sc_ref[2, 0] * g
    v_new = sc_ref[3, 0] * v_ref[...] + sc_ref[4, 0] * (g * g)
    u = (m_new * sc_ref[5, 0]) / (jnp.sqrt(v_new * sc_ref[6, 0]) + eps)
    if weight_decay != 0.0:
        u = u + weight_decay * p
    m_out[...] = m_new.astype(m_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)
    u_out[...] = u.astype(u_out.dtype)


def _lamb_phase1_seg_kernel(m_ref, v_ref, g_ref, p_ref, sc_ref, lo_ref,
                            hi_ref, vals_ref, off_ref, m_out, v_out,
                            u_out, *, eps, npad, R):
    """Phase 1 with PER-TENSOR weight decay (vals row 0), rebuilt per
    block from segment row bounds — the LAMB consumer of
    get_params_for_weight_decay_optimization's no-decay mask (lr scale
    rides in phase 2's per-tensor ratio, zero extra work)."""
    i = pl.program_id(0)
    oh = _block_onehot(lo_ref, hi_ref, off_ref, i, R, npad)
    wd_row = jax.lax.dot_general(oh, vals_ref[0:1, :],
                                 (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    g = jnp.where(sc_ref[7, 0] > 0.5, 0.0, g * sc_ref[0, 0])
    m_new = sc_ref[1, 0] * m_ref[...] + sc_ref[2, 0] * g
    v_new = sc_ref[3, 0] * v_ref[...] + sc_ref[4, 0] * (g * g)
    u = (m_new * sc_ref[5, 0]) / (jnp.sqrt(v_new * sc_ref[6, 0]) + eps)
    u = u + wd_row * p
    m_out[...] = m_new.astype(m_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)
    u_out[...] = u.astype(u_out.dtype)


def _lamb_phase2_kernel(p_ref, u_ref, r_ref, sc_ref, p_out):
    """Phase 2 ≡ multi_tensor_lamb_stage2: p -= lr * trust_ratio * u, with
    the per-element trust-ratio vector r."""
    lr = sc_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    p_out[...] = (p - lr * r_ref[...] * u_ref[...]).astype(p_out.dtype)


def _lamb_fold_scalars(clip_ratio, step, beta1, beta2, bias_correction,
                       grad_averaging, inv_scale, found_inf):
    """The ONE definition of the LAMB phase-1 folded-scalar rows
    (shared by the uniform and per-tensor-seg variants)."""
    beta3 = (1.0 - beta1) if grad_averaging else 1.0
    step = jnp.asarray(step, jnp.float32)
    bc1 = jnp.maximum(1.0 - jnp.power(jnp.float32(beta1), step), 1e-20)
    bc2 = jnp.maximum(1.0 - jnp.power(jnp.float32(beta2), step), 1e-20)
    one = jnp.float32(1.0)
    keep = jnp.asarray(found_inf).astype(jnp.bool_)
    g_scale = (jnp.asarray(clip_ratio, jnp.float32)
               * jnp.asarray(inv_scale, jnp.float32))
    return jnp.stack([
        g_scale,
        jnp.where(keep, one, jnp.float32(beta1)),          # b1e
        jnp.where(keep, 0.0, jnp.float32(beta3)),          # c1
        jnp.where(keep, one, jnp.float32(beta2)),          # b2e
        jnp.where(keep, 0.0, 1.0 - jnp.float32(beta2)),    # c2
        one / bc1 if bias_correction else one,             # rbc1
        one / bc2 if bias_correction else one,             # rbc2
        keep.astype(jnp.float32),                          # found
    ]).reshape(8, 1)


def lamb_phase1_flat(m, v, g, p, clip_ratio, step, *, beta1, beta2, eps,
                     weight_decay, bias_correction=True,
                     grad_averaging=True, inv_scale=1.0, found_inf=False,
                     use_pallas_override=None):
    """`g` may ride in its native (bf16) dtype — the kernel upcasts per
    block.  inv_scale and the overflow skip are folded into the scalar
    rows (≡ the capturable CUDA-graph LAMB), so callers need no extra
    whole-buffer passes for unscale or skip-masking."""
    scalars = _lamb_fold_scalars(clip_ratio, step, beta1, beta2,
                                 bias_correction, grad_averaging,
                                 inv_scale, found_inf)
    if not use_pallas(use_pallas_override):
        g32 = jnp.where(scalars[7, 0] > 0.5, 0.0,
                        g.astype(jnp.float32) * scalars[0, 0])
        p32 = p.astype(jnp.float32)
        m_new = scalars[1, 0] * m + scalars[2, 0] * g32
        v_new = scalars[3, 0] * v + scalars[4, 0] * (g32 * g32)
        u = (m_new * scalars[5, 0]) / (
            jnp.sqrt(v_new * scalars[6, 0]) + eps)
        if weight_decay:
            u = u + weight_decay * p32
        return (m_new.astype(m.dtype), v_new.astype(v.dtype),
                u.astype(p.dtype))
    kernel = functools.partial(
        _lamb_phase1_kernel, eps=eps, weight_decay=weight_decay)
    m2, n = _to2d(m)
    v2, _ = _to2d(v)
    g2, _ = _to2d(g)
    p2, _ = _to2d(p)
    R = _block_rows(m2.shape[0], "lamb1")
    grid = m2.shape[0] // R
    spec = pl.BlockSpec((R, _LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((8, 1), lambda i: (0, 0))
    with kernel_span("lamb_phase1"):
        mn, vn, u = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[spec, spec, spec, spec, sspec],
            out_specs=[spec, spec, spec],
            # m/v aliased in place (dtypes preserved); u rides in the
            # master dtype so a bf16-state LAMB halves the u write + the
            # norm-pass and phase-2 reads (≡ the 1.3B Adam bf16-state point)
            out_shape=[jax.ShapeDtypeStruct(m2.shape, m2.dtype),
                       jax.ShapeDtypeStruct(v2.shape, v2.dtype),
                       jax.ShapeDtypeStruct(p2.shape, p2.dtype)],
            input_output_aliases={0: 0, 1: 1},
            interpret=pallas_interpret(),
            name="lamb_phase1",
        )(m2, v2, g2, p2, scalars)
    return _from2d(mn, n), _from2d(vn, n), _from2d(u, n)


def lamb_phase1_seg(m, v, g, p, clip_ratio, step, *, wd_values, spec,
                    row_offset=0, padded_total=None, beta1, beta2, eps,
                    bias_correction=True, grad_averaging=True,
                    inv_scale=1.0, found_inf=False,
                    use_pallas_override=None):
    """lamb_phase1_flat with a per-tensor weight-decay vector expanded
    in-kernel from the (lane-aligned) spec's row bounds."""
    scalars = _lamb_fold_scalars(clip_ratio, step, beta1, beta2,
                                 bias_correction, grad_averaging,
                                 inv_scale, found_inf)
    wd_values = jnp.asarray(wd_values, jnp.float32)
    n_seg = wd_values.shape[0]
    npad = _seg_pad(n_seg)
    if not (use_pallas(use_pallas_override) and n_seg + 1 < _SEG_CAP
            and p.shape[0] % FLAT_TILE == 0):
        rows = p.shape[0] // _LANES
        total = padded_total if padded_total is not None else p.shape[0]
        rank = jnp.asarray(row_offset, jnp.int32) // rows
        seg = shard_segment_ids(spec, rank, rows, total)
        wd_elem = expand_per_tensor_shard(wd_values, seg)
        g32 = jnp.where(scalars[7, 0] > 0.5, 0.0,
                        g.astype(jnp.float32) * scalars[0, 0])
        p32 = p.astype(jnp.float32)
        m_new = scalars[1, 0] * m + scalars[2, 0] * g32
        v_new = scalars[3, 0] * v + scalars[4, 0] * (g32 * g32)
        u = (m_new * scalars[5, 0]) / (
            jnp.sqrt(v_new * scalars[6, 0]) + eps)
        u = u + wd_elem * p32
        return (m_new.astype(m.dtype), v_new.astype(v.dtype),
                u.astype(p.dtype))
    m2, n = _to2d(m)
    v2, _ = _to2d(v)
    g2, _ = _to2d(g)
    p2, _ = _to2d(p)
    R = _block_rows(m2.shape[0], "lamb1_seg")
    grid = m2.shape[0] // R
    lo, hi = _seg_row_bounds(spec, npad)
    vals8 = jnp.zeros((8, npad), jnp.float32).at[0, :n_seg].set(wd_values)
    off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
    bspec = pl.BlockSpec((8, npad), lambda i: (0, 0))
    spec_b = pl.BlockSpec((R, _LANES), lambda i: (i, 0))
    with kernel_span("lamb_phase1_seg"):
        mn, vn, u = pl.pallas_call(
            functools.partial(_lamb_phase1_seg_kernel, eps=eps, npad=npad,
                              R=R),
            grid=(grid,),
            in_specs=[spec_b, spec_b, spec_b, spec_b,
                      pl.BlockSpec((8, 1), lambda i: (0, 0)),
                      bspec, bspec, bspec,
                      pl.BlockSpec((1, 1), lambda i: (0, 0))],
            out_specs=[spec_b, spec_b, spec_b],
            out_shape=[jax.ShapeDtypeStruct(m2.shape, m2.dtype),
                       jax.ShapeDtypeStruct(v2.shape, v2.dtype),
                       jax.ShapeDtypeStruct(p2.shape, p2.dtype)],
            input_output_aliases={0: 0, 1: 1},
            interpret=pallas_interpret(),
            name="lamb_phase1_seg",
        )(m2, v2, g2, p2, scalars, lo, hi, vals8, off)
    return _from2d(mn, n), _from2d(vn, n), _from2d(u, n)


def _lamb_phase2_seg_kernel(p_ref, u_ref, lo_ref, hi_ref, vals_ref,
                            sc_ref, off_ref, p_out, *, npad, R):
    """Phase 2 with IN-KERNEL trust-ratio expansion: the per-tensor
    ratio row vector is rebuilt per block via the bounds one-hot matmul
    (same trick as _rows_sumsq_seg_kernel, transposed) — the (total,)
    per-element ratio vector never exists in HBM."""
    i = pl.program_id(0)
    lr = sc_ref[0, 0]
    oh = _block_onehot(lo_ref, hi_ref, off_ref, i, R, npad)
    # exactly one 1 per row → this dot is a SELECT of vals; HIGHEST
    # keeps the selected fp32 ratio exact (default = bf16 rounding)
    ratio_row = jax.lax.dot_general(
        oh, vals_ref[0:1, :], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)              # (R, 1)
    p = p_ref[...].astype(jnp.float32)
    p_out[...] = (p - lr * ratio_row * u_ref[...]).astype(p_out.dtype)


def lamb_phase2_seg(p, u, ratio_values, spec, lr, *, row_offset=0,
                    padded_total=None, use_pallas_override=None):
    """p -= lr * trust_ratio[tensor] * u with per-tensor `ratio_values`
    ((n_seg,)) expanded in-kernel from the spec's static row bounds.
    `row_offset` is p's global starting row (rank*shard_rows for a
    shard; may be traced — `padded_total` must then be given for the
    fallback's segment map).  Rows outside every tensor (tail padding)
    get ratio 0, leaving them untouched."""
    n_seg = ratio_values.shape[0]
    npad = _seg_pad(n_seg)
    if not (use_pallas(use_pallas_override) and n_seg + 1 < _SEG_CAP
            and p.shape[0] % FLAT_TILE == 0):
        rows = p.shape[0] // _LANES
        total = padded_total if padded_total is not None else p.shape[0]
        rank = jnp.asarray(row_offset, jnp.int32) // rows
        seg = shard_segment_ids(spec, rank, rows, total)
        vals = jnp.concatenate(
            [ratio_values.astype(jnp.float32),
             jnp.zeros((1,), jnp.float32)])  # dummy tail ratio 0
        per_row = vals[seg]
        ratio_elem = jnp.broadcast_to(
            per_row[:, None], (per_row.shape[0], _LANES)).reshape(-1)
        return lamb_phase2_flat(p, u, ratio_elem, lr,
                                use_pallas_override=use_pallas_override)
    p2, n = _to2d(p)
    u2, _ = _to2d(u)
    R = _block_rows(p2.shape[0], "lamb2_seg")
    nb = p2.shape[0] // R
    lo, hi = _seg_row_bounds(spec, npad)
    vals8 = jnp.broadcast_to(
        jnp.pad(ratio_values.astype(jnp.float32),
                (0, npad - n_seg))[None, :], (8, npad))
    scalars = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
    bspec = pl.BlockSpec((8, npad), lambda i: (0, 0))
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    with kernel_span("lamb_phase2_seg"):
        pn = pl.pallas_call(
            functools.partial(_lamb_phase2_seg_kernel, npad=npad, R=R),
            grid=(nb,),
            in_specs=[pl.BlockSpec((R, _LANES), lambda i: (i, 0)),
                      pl.BlockSpec((R, _LANES), lambda i: (i, 0)),
                      bspec, bspec, bspec, sspec, sspec],
            out_specs=pl.BlockSpec((R, _LANES), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(p2.shape, p2.dtype),
            input_output_aliases={0: 0},
            interpret=pallas_interpret(),
            name="lamb_phase2_seg",
        )(p2, u2, lo, hi, vals8, scalars, off)
    return _from2d(pn, n)


def lamb_phase2_flat(p, u, ratio_elem, lr, use_pallas_override=None):
    scalars = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    if not use_pallas(use_pallas_override):
        return (p.astype(jnp.float32) - scalars[0, 0] * ratio_elem * u
                ).astype(p.dtype)
    p2, n = _to2d(p)
    u2, _ = _to2d(u)
    r2, _ = _to2d(ratio_elem)
    R = _block_rows(p2.shape[0], "lamb2")
    grid = p2.shape[0] // R
    spec = pl.BlockSpec((R, _LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    with kernel_span("lamb_phase2"):
        pn = pl.pallas_call(
            _lamb_phase2_kernel,
            grid=(grid,),
            in_specs=[spec, spec, spec, sspec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(p2.shape, p2.dtype),
            input_output_aliases={0: 0},
            interpret=pallas_interpret(),
            name="lamb_phase2",
        )(p2, u2, r2, scalars)
    return _from2d(pn, n)


# --------------------------- reductions / utilities -------------------------

def l2norm_flat(flat):
    """Global L2 norm ≡ amp_C.multi_tensor_l2norm (csrc/multi_tensor_l2norm_kernel.cu).
    XLA lowers this to an optimal tree reduction; no Pallas needed."""
    return jnp.sqrt(jnp.sum(jnp.square(flat.astype(jnp.float32))))


def per_tensor_l2norm(flat, sizes):
    """Per-tensor norms over a flat buffer ≡ multi_tensor_l2norm
    per_tensor=True mode.  `sizes` is the static segment-length list."""
    norms = []
    off = 0
    for s in sizes:
        seg = jax.lax.dynamic_slice(flat, (off,), (s,))
        norms.append(jnp.sqrt(jnp.sum(jnp.square(seg.astype(jnp.float32)))))
        off += s
    return jnp.stack(norms)


def expand_per_tensor(values, sizes, total):
    """Broadcast per-tensor scalars to per-element vector (static sizes)."""
    return jnp.repeat(values, jnp.asarray(sizes), total_repeat_length=total)


def scale_flat(flat, scale):
    """≡ amp_C.multi_tensor_scale: scaled copy; overflow check is fused by
    XLA into the same pass when consumed with jnp.isfinite."""
    return flat.astype(jnp.float32) * scale


def axpby_flat(a, x, b, y):
    """≡ amp_C.multi_tensor_axpby: a*x + b*y."""
    return a * x.astype(jnp.float32) + b * y.astype(jnp.float32)


# --- row-aligned per-tensor reductions (128-lane-aligned FlatSpec) ----------
#
# With FlatSpec(align=_LANES) every tensor's segment spans whole rows of
# the (rows, 128) 2-D view (zero-filled tails), so multi_tensor_l2norm's
# per_tensor mode becomes: one squared-row-sum pass + one static
# segment-sum — instead of one dynamic_slice+reduction per tensor (which
# at BERT/GPT scale is ~600 serialized slices over the whole buffer).

def _row_segment_ids(spec):
    import numpy as _np
    # segment extents come straight from the spec's (aligned) offsets so
    # this can never drift from make_spec's padding rule
    bounds = list(spec.offsets) + [spec.total]
    rows = [(bounds[i + 1] - bounds[i]) // _LANES
            for i in range(len(spec.offsets))]
    return _np.repeat(_np.arange(len(rows), dtype=_np.int32), rows)


# Per-tensor segment reductions over the flat buffer.  TPU scatter (the
# jax.ops.segment_sum lowering) and big gathers are VPU-serial — at
# BERT-Large scale one segment_sum over 2.6M rows measured 36 ms and the
# values[seg] expand gather 35 ms, dwarfing the optimizer math itself.
# Segments are CONTIGUOUS row runs, so each (rows, 128) block can turn
# its row sums into per-tensor partials with ONE one-hot matmul on the
# MXU ((R, 1)^T-dot-(R, n_seg) from an iota==seg compare); a VMEM
# accumulator carries partials across the sequential grid.  ≡ the
# two-phase multi_tensor_l2norm reduction (csrc/multi_tensor_l2norm.cu)
# re-shaped for the MXU.

_SEG_CAP = 2048  # one-hot width cap; fall back to segment_sum beyond


def _seg_pad(n_seg):
    return max(_LANES, -(-(n_seg + 1) // _LANES) * _LANES)


def _seg_row_bounds(spec, npad):
    """Per-tensor [start, end) ROW bounds as (8, npad) int32 blocks (row
    0 is real; broadcast to the fp32 min-tile height).  The contiguous
    layout means segment membership is two compares against these
    bounds — no per-row segment-id array, no gather.  Unused columns get
    a sentinel past any row index."""
    import numpy as _np
    assert spec.align % _LANES == 0, "spec must be lane-aligned"
    n_seg = len(spec.sizes)
    starts = _np.full((npad,), 2 ** 30, _np.int32)
    ends = _np.full((npad,), 2 ** 30, _np.int32)
    bounds = list(spec.offsets) + [spec.total]
    for s in range(n_seg):
        starts[s] = bounds[s] // _LANES
        ends[s] = bounds[s + 1] // _LANES
    lo = jnp.broadcast_to(jnp.asarray(starts)[None, :], (8, npad))
    hi = jnp.broadcast_to(jnp.asarray(ends)[None, :], (8, npad))
    return lo, hi


def _block_onehot(lo_ref, hi_ref, off_ref, i, R, npad):
    """(R, npad) one-hot of global-row-in-segment for grid block i."""
    rowg = (off_ref[0, 0] + i * R
            + lax.broadcasted_iota(jnp.int32, (R, 1), 0))
    return ((rowg >= lo_ref[0:1, :]) & (rowg < hi_ref[0:1, :])
            ).astype(jnp.float32)


def _rows_sumsq_seg_kernel(x_ref, lo_ref, hi_ref, off_ref, out_ref, acc,
                           *, nb, npad, R):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xb = x_ref[...].astype(jnp.float32)
    rq = jnp.sum(xb * xb, axis=1, keepdims=True)            # (R, 1)
    oh = _block_onehot(lo_ref, hi_ref, off_ref, i, R, npad)
    # HIGHEST: the default MXU fp32 path is a single bf16 pass, which
    # rounds the row sums to ~8 mantissa bits — trust ratios then drift
    # ~4e-4 vs the jnp oracle
    acc[0:1, :] += jax.lax.dot_general(
        rq, oh, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(i == nb - 1)
    def _done():
        out_ref[...] = acc[...]


def _per_tensor_sumsq_2d(x2, spec, n_seg, row_offset):
    """(rows, 128) buffer → (n_seg,) sums of squares via per-block
    one-hot matmuls.  `row_offset` is this buffer's global starting row
    (0 for a full buffer; rank*shard_rows for a shard — may be traced)."""
    rows = x2.shape[0]
    R = _block_rows(rows, "sumsq_seg")
    nb = rows // R
    npad = _seg_pad(n_seg)
    lo, hi = _seg_row_bounds(spec, npad)
    off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
    bspec = pl.BlockSpec((8, npad), lambda i: (0, 0))
    with kernel_span("per_tensor_sumsq"):
        out = pl.pallas_call(
            functools.partial(_rows_sumsq_seg_kernel, nb=nb, npad=npad, R=R),
            grid=(nb,),
            in_specs=[pl.BlockSpec((R, _LANES), lambda i: (i, 0)),
                      bspec, bspec,
                      pl.BlockSpec((1, 1), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, npad), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, npad), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, npad), jnp.float32)],
            interpret=pallas_interpret(),
            name="per_tensor_sumsq",
        )(x2, lo, hi, off)
    return out[0, :n_seg]


def per_tensor_l2norm_aligned(flat, spec, use_pallas_override=None):
    """Per-tensor L2 norms over a lane-aligned flat buffer; `spec.align`
    must be a multiple of the 128-lane width."""
    assert spec.align % _LANES == 0, "spec must be lane-aligned"
    n_seg = len(spec.sizes)
    if (use_pallas(use_pallas_override) and n_seg < _SEG_CAP
            and flat.shape[0] % FLAT_TILE == 0):
        x2 = flat.reshape(-1, _LANES)
        return jnp.sqrt(_per_tensor_sumsq_2d(x2, spec, n_seg, 0))
    x2 = flat[: spec.total].reshape(-1, _LANES).astype(jnp.float32)
    rowsq = jnp.sum(x2 * x2, axis=1)                      # (rows,)
    seg = jnp.asarray(_row_segment_ids(spec))             # static constant
    sums = jax.ops.segment_sum(rowsq, seg,
                               num_segments=len(spec.sizes))
    return jnp.sqrt(sums)


def expand_per_tensor_aligned(values, spec, total):
    """Broadcast per-tensor scalars to a per-element vector of `total`
    length (>= spec.total; the tail repeats the last value, harmless on
    zero padding)."""
    assert spec.align % _LANES == 0
    seg = jnp.asarray(_row_segment_ids(spec))
    per_row = values[seg]                                  # (rows,)
    elem = jnp.broadcast_to(per_row[:, None],
                            (per_row.shape[0], _LANES)).reshape(-1)
    if total > elem.shape[0]:
        elem = jnp.concatenate(
            [elem, jnp.broadcast_to(values[-1], (total - elem.shape[0],))])
    return elem


def _row_segment_ids_padded(spec, rows_total):
    """Row→tensor map over the PADDED buffer (rows_total >= spec rows):
    tail padding rows get the dummy id len(spec.sizes)."""
    import numpy as _np
    base = _row_segment_ids(spec)
    pad = rows_total - base.shape[0]
    return _np.concatenate(
        [base, _np.full((pad,), len(spec.sizes), _np.int32)])


def shard_segment_ids(spec, rank, rows_shard, padded_total):
    """This rank's slice of the padded row→tensor map (tail padding rows
    get the dummy id len(spec.sizes)).  The shard is a contiguous flat
    slice [rank*S, (rank+1)*S) with S a multiple of FLAT_TILE, so its
    rows are a contiguous run of the global row map — a dynamic slice at
    a traced `rank` is all it takes.  Compute ONCE per step and pass to
    the per-tensor helpers below (the full row map is O(params/128))."""
    assert spec.align % _LANES == 0
    seg_full = jnp.asarray(
        _row_segment_ids_padded(spec, padded_total // _LANES))
    return jax.lax.dynamic_slice(seg_full, (rank * rows_shard,),
                                 (rows_shard,))


def per_tensor_sumsq_shard(shard, spec, rank, padded_total,
                           use_pallas_override=None):
    """Per-tensor PARTIAL sums of squares over ONE rank's contiguous
    flat shard (shards partition the `padded_total`-long buffer evenly;
    `rank` may be traced).  A psum over the shard axis yields the exact
    full-buffer per-tensor sums — no rank ever materializes the full
    buffer (≡ the reference's pipelined block-reduction L2 norms,
    distributed_fused_lamb.py:728-987, which exist for the same reason).
    Returns (n_tensors,) fp32 partial sums; tail-padding rows fall
    outside every bound and contribute nothing."""
    n_seg = len(spec.sizes)
    rows_shard = shard.shape[0] // _LANES
    if (use_pallas(use_pallas_override) and n_seg + 1 < _SEG_CAP
            and shard.shape[0] % FLAT_TILE == 0):
        x2 = shard.reshape(-1, _LANES)
        return _per_tensor_sumsq_2d(x2, spec, n_seg, rank * rows_shard)
    seg = shard_segment_ids(spec, rank, rows_shard, padded_total)
    x2 = shard.reshape(-1, _LANES).astype(jnp.float32)
    rowsq = jnp.sum(x2 * x2, axis=1)                      # (rows,)
    sums = jax.ops.segment_sum(rowsq, seg,
                               num_segments=len(spec.sizes) + 1)
    return sums[: len(spec.sizes)]


def expand_per_tensor_shard(values, seg):
    """Broadcast per-tensor scalars to ONE rank's shard elements —
    the shard-local counterpart of expand_per_tensor_aligned (padding
    rows broadcast 0.0, matching the lamb_phase2_seg / one-hot kernel
    convention for the padding segment).  Prefer lamb_phase2_seg, which
    folds the expansion into the update kernel and never materializes
    the per-element vector."""
    rows_shard = seg.shape[0]
    vals = jnp.concatenate(
        [values.astype(jnp.float32), jnp.zeros((1,), jnp.float32)])
    per_row = vals[seg]                                    # (rows,)
    return jnp.broadcast_to(per_row[:, None],
                            (rows_shard, _LANES)).reshape(-1)
