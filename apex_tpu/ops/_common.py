"""Shared kernel-layer plumbing.

≡ the reference's shared native infrastructure (csrc/type_shim.h dtype
dispatch, csrc/compat.h): here it is backend dispatch — every fused op
has a Pallas TPU kernel and a pure-jnp reference implementation; on
non-TPU backends (CPU tests, interpret mode) the jnp path is used, the
same way the reference falls back to pure PyTorch when the extension is
absent (apex/normalization/fused_layer_norm.py:288-294).
"""

from __future__ import annotations

import contextlib
import os
import threading

import jax
import jax.numpy as jnp

_FORCE = os.environ.get("APEX_TPU_FORCE_PALLAS", "")


def on_chip() -> bool:
    """The one answer to "am I on the chip": the default backend's
    devices are TPUs.  Kernel dispatch, interpret mode, the tuner's
    device kind, bench.py, the probes and chip_smoke.py all ask here."""
    return jax.devices()[0].platform == "tpu"


def use_pallas(override=None) -> bool:
    """Decide kernel path: Pallas on TPU, jnp reference elsewhere.

    `override`: True → pallas (interpret-mode off-TPU), False → jnp.
    Env APEX_TPU_FORCE_PALLAS=1/0 wins over the backend default.
    """
    if override is not None:
        return override
    if _FORCE == "1":
        return True
    if _FORCE == "0":
        return False
    return on_chip()


def use_pallas_fusable(override=None) -> bool:
    """use_pallas for ops where XLA's automatic fusion usually wins.

    Memory-bound elementwise ops (LayerNorm/RMSNorm) fuse into their
    neighboring producers/consumers under XLA; a standalone Pallas
    kernel puts a custom_vjp/custom-call boundary in the way and costs
    a full extra HBM round trip (measured on v5e: GPT-350M step 41.9k
    -> 44.5k tok/s from letting XLA fuse the 49 LayerNorms).  The
    Pallas kernel remains available via override=True or
    APEX_TPU_FORCE_PALLAS=1 (and is what interpret-mode parity tests
    pin).
    """
    if override is not None:
        return override
    return _FORCE == "1"


def pallas_interpret() -> bool:
    """Pallas kernels run in interpret mode off-TPU (for CPU CI parity)."""
    return not on_chip()


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


LANES = 128          # a vreg's lanes: the last dimension's tile


def fills_lane_tiles(d: int) -> bool:
    """Whether a head `d` wide goes to a kernel whose block's last
    dimension is the head's whole width, which Mosaic pads to lane
    tiles in VMEM: a multiple of 32 that fills at least three quarters
    of its tiles.  96, 192 and the multiples of 128 are what the chip
    has run; a 64-wide head would leave half of every tile empty."""
    return d % 32 == 0 and 4 * d >= 3 * round_up(d, LANES)


def row_block(rows: int, hidden: int, bytes_per_elt: int = 4,
              vmem_budget: int = 2 * 1024 * 1024, align: int = 8,
              cap: int = 1024) -> int:
    """Pick a row-block size so a (block, hidden) fp32 tile fits the VMEM
    budget; aligned to the fp32 sublane (8)."""
    b = max(align, vmem_budget // max(1, hidden * bytes_per_elt))
    b = min(b, cap, round_up(rows, align))
    return round_up(b, align) if b % align else b


def tuned_row_block(op: str, rows: int, hidden: int, **kw) -> int:
    """row_block with an autotuner override: consult apex_tpu.tune for
    (op, pow2-bucketed rows, hidden) on this device kind; a hit whose
    block_rows is a sane sublane multiple wins, anything else falls
    back to the deterministic heuristic.  Trace-time host-side lookup
    only — no device work (tune package docstring)."""
    from apex_tpu import tune

    base = row_block(rows, hidden, **kw)
    cfg = tune.tuned(op, dict(rows=tune.pow2_bucket(rows), hidden=hidden))
    if cfg:
        blk = cfg.get("block_rows")
        if (isinstance(blk, int) and 8 <= blk <= 4096 and blk % 8 == 0):
            return blk
    return base


# --------------------------- numerics taps ---------------------------
#
# The flight-recorder tap op (monitor/trace, ISSUE 4).  It lives here —
# not in monitor/ — because the models call `tap()` on their hot path
# and must not import the monitor package (which pulls sinks/logger);
# ops._common is already in their import closure (dropout above).
#
# Contract: `tap(x, name)` is a BYTE-IDENTICAL identity when no
# TapContext is active (the default) — it returns `x` itself before
# tracing ever sees a new op, so untapped programs compile unchanged.
# Under an active context every tap draws a zeros (2, 4) row from the
# context's `probes` array and BOTH stat planes flow out through that
# row's *gradient*: `grad_tap`'s custom_vjp saves `tap_stats(x)` as a
# residual and returns it stacked with `tap_stats(cotangent)` as the
# probe's cotangent.  Differentiating the loss w.r.t. `probes` then
# yields (n_taps, 2, 4) = per-tap [fwd, grad] stats with no side
# channels, no host callbacks, and no collectives — and because no
# traced value ever lands in Python state, taps are safe inside
# jax.checkpoint/remat regions and lax control flow.

TAP_STAT_FIELDS = ("absmax", "mean", "rms", "nonfinite")
TAP_STAT_DIM = len(TAP_STAT_FIELDS)
TAP_PLANES = ("fwd", "grad")


def tap_stats(x) -> jnp.ndarray:
    """f32[4] = [absmax, mean, rms, nonfinite-element count] of x.

    Computed in f32; when x holds non-finite values the first three
    lanes are themselves non-finite (NaN propagates through max/mean)
    while lane 3 — the count — is always finite and is what provenance
    keys on."""
    xf = x.astype(jnp.float32)
    return jnp.stack([
        jnp.max(jnp.abs(xf)),
        jnp.mean(xf),
        jnp.sqrt(jnp.mean(jnp.square(xf))),
        jnp.sum(~jnp.isfinite(xf)).astype(jnp.float32),
    ])


@jax.custom_vjp
def grad_tap(x, probe):
    """Identity on x whose backward writes stacked
    [tap_stats(x), tap_stats(cotangent)] into `probe`'s gradient
    (probe: f32[2, 4] zeros drawn from TapContext)."""
    del probe
    return x


def _grad_tap_fwd(x, probe):
    del probe
    return x, tap_stats(x)


def _grad_tap_bwd(fwd_stats, g):
    return g, jnp.stack([fwd_stats, tap_stats(g)])


grad_tap.defvjp(_grad_tap_fwd, _grad_tap_bwd)


class TapContext:
    """Assigns probe rows to tap points for one trace.

    probes: f32[max_taps, 2, 4] zeros — an ARGUMENT of the caller's
    jax.grad so each tap's [fwd, grad] stats land in its row (see
    grad_tap).  Rows are assigned in forward trace order; `names[i]`
    labels row i (host-side strings, read after jax.grad returns).
    `discover=True` records names only (no probe draw) for shape-free
    tap enumeration."""

    def __init__(self, probes=None, discover: bool = False):
        self.probes = probes
        self.discover = discover
        self.names = []

    @property
    def max_taps(self) -> int:
        return 0 if self.probes is None else int(self.probes.shape[0])


_ACTIVE_TAPS = threading.local()


def active_tap_context():
    return getattr(_ACTIVE_TAPS, "ctx", None)


@contextlib.contextmanager
def tap_context(ctx: TapContext):
    prev = active_tap_context()
    _ACTIVE_TAPS.ctx = ctx
    try:
        yield ctx
    finally:
        _ACTIVE_TAPS.ctx = prev


def tap(x, name: str):
    """Named numerics tap point.  No active TapContext (the default):
    returns x itself — zero cost, compiled out.  Active: arms the
    [fwd, grad] stats probe for this point."""
    ctx = active_tap_context()
    if ctx is None:
        return x
    i = len(ctx.names)
    ctx.names.append(str(name))
    if ctx.discover:
        return x
    if i >= ctx.max_taps:
        raise ValueError(
            f"tap {name!r} is tap #{i + 1} but the TapContext probes "
            f"array holds {ctx.max_taps} rows; raise "
            "TraceConfig.max_taps")
    return grad_tap(x, ctx.probes[i])


def dropout(key, rate: float, x):
    """Inverted-bernoulli dropout: zero with probability `rate`, scale
    survivors by 1/(1-rate).  The ONE implementation shared by the dense
    attention oracle, the models, and contrib modules so their dropout
    semantics can never diverge (the flash kernel's in-kernel
    counter-based mask is its hardware-PRNG counterpart)."""
    if rate == 0.0 or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    import jax.numpy as jnp
    return jnp.where(mask, x / keep, jnp.zeros((), x.dtype))
