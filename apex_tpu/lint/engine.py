"""The shared jaxpr-walking engine behind the program-level lint passes.

veScale's single-controller posture (arxiv 2509.07003) argues the SPMD
program should be *verified before execution*; under JAX the closed
jaxpr of the train step IS that program, available without a device or
a compile.  This module traces a step (ShapeDtypeStructs suffice — the
same contract as `monitor.analyze_step`), flattens every sub-jaxpr
(pjit / shard_map / scan / while / cond / custom-vjp / remat) into
`JaxprView`s carrying the context the passes need — the jaxpr path,
the mesh axes bound by enclosing shard_maps, whether the jaxpr is a
scan body and which of its invars are loop-invariant — and runs the
registered passes over them.

`lint_step` is the high-level entry: it reads the builder-attached
metadata (`step.arg_names`, `step.donate_argnums`,
`step.mesh_axis_names` — `ddp.make_train_step` and
`make_tp_dp_train_step` attach all three), traces the exact program the
step would run, and returns the combined findings of the dtype-policy,
collective, and donation passes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence

import jax

from jax.extend.core import Literal as _Literal

from apex_tpu.lint.findings import Finding

# collective primitives (by jaxpr name) the collective pass reasons
# about.  pmean does not appear: it traces to psum + div.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "pbroadcast", "reduce_scatter", "psum_scatter",
})

# low-precision float dtypes (by numpy name) for the dtype passes
LOW_PRECISION = frozenset({"bfloat16", "float16", "float8_e4m3fn",
                           "float8_e5m2"})


@dataclasses.dataclass
class JaxprView:
    """One (sub-)jaxpr plus the traversal context the passes need."""

    jaxpr: Any                     # the OPEN jaxpr (has .eqns/.invars)
    path: str                      # e.g. "pjit/shard_map/scan"
    axes: frozenset                # mesh axes bound by enclosing scopes
    scan_num_consts: Optional[int]  # set when this jaxpr is a scan body
    depth: int

    def eqn_location(self, program: str, eqn, index: int) -> str:
        """Stable-ish allowlist location: program, jaxpr path, primitive
        name and its ordinal AMONG SAME-PRIMITIVE eqns in this jaxpr
        (an unrelated edit inserting eqns of other primitives does not
        shift it)."""
        return f"{program}:{self.path}:{eqn.primitive.name}[{index}]"


def _is_jaxpr(obj) -> bool:
    return hasattr(obj, "eqns") and hasattr(obj, "invars")


def _open(obj):
    """ClosedJaxpr -> its open jaxpr; open jaxprs pass through."""
    inner = getattr(obj, "jaxpr", None)
    return inner if inner is not None and _is_jaxpr(inner) else obj


def _sub_jaxprs(eqn):
    """Yield (tag, jaxpr-like) for every sub-jaxpr riding in the eqn's
    params — generic over primitive (pjit 'jaxpr', scan 'jaxpr', cond
    'branches', while 'cond_jaxpr'/'body_jaxpr', custom-vjp
    'call_jaxpr'/'fun_jaxpr', shard_map 'jaxpr', remat 'jaxpr')."""
    for key, val in eqn.params.items():
        if _is_jaxpr(val) or _is_jaxpr(getattr(val, "jaxpr", None)):
            yield key, val
        elif isinstance(val, (tuple, list)):
            for i, item in enumerate(val):
                if _is_jaxpr(item) or _is_jaxpr(getattr(item, "jaxpr",
                                                        None)):
                    yield f"{key}[{i}]", item


def _eqn_axes(eqn) -> frozenset:
    """Mesh axes an eqn's scope binds (shard_map's mesh / pmap's
    axis_name), collected defensively across jax versions."""
    axes = set()
    mesh = eqn.params.get("mesh")
    names = getattr(mesh, "axis_names", None)
    if names:
        axes.update(str(n) for n in names)
    for key in ("axis_name", "axis"):
        v = eqn.params.get(key)
        if isinstance(v, str):
            axes.add(v)
        elif isinstance(v, (tuple, list)):
            axes.update(str(n) for n in v)
    for key in ("in_names", "out_names"):
        v = eqn.params.get(key)
        if isinstance(v, (tuple, list)):
            for d in v:
                if isinstance(d, dict):
                    for nm in d.values():
                        if isinstance(nm, (tuple, list)):
                            axes.update(str(n) for n in nm)
                        else:
                            axes.add(str(nm))
    return frozenset(axes)


def collect_views(closed_jaxpr, *, base_axes=frozenset(),
                  max_depth: int = 32) -> List[JaxprView]:
    """Flatten a (closed) jaxpr and every sub-jaxpr into JaxprViews,
    outermost first."""
    views: List[JaxprView] = []

    def walk(jx, path, axes, scan_consts, depth):
        jx = _open(jx)
        views.append(JaxprView(jaxpr=jx, path=path, axes=axes,
                               scan_num_consts=scan_consts, depth=depth))
        if depth >= max_depth:
            return
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            child_axes = axes | _eqn_axes(eqn)
            for tag, sub in _sub_jaxprs(eqn):
                child_consts = None
                if prim == "scan" and tag == "jaxpr":
                    child_consts = int(eqn.params.get("num_consts", 0))
                walk(sub, f"{path}/{prim}" if path else prim,
                     child_axes, child_consts, depth + 1)

    walk(closed_jaxpr, "", frozenset(base_axes), None, 0)
    return views


def used_vars(jaxpr) -> set:
    """Vars of `jaxpr` that feed an eqn or the jaxpr outputs (dead-code
    detection; make_jaxpr keeps dead eqns — DCE is a lowering pass)."""
    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, _Literal):
                used.add(v)
    for v in jaxpr.outvars:
        if not isinstance(v, _Literal):
            used.add(v)
    return used


def producers(jaxpr) -> dict:
    """var -> producing eqn map for one jaxpr level."""
    out = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out[v] = eqn
    return out


def invariant_vars(view: JaxprView) -> set:
    """For a scan-body view: the vars that are loop-invariant (derive
    only from scan consts, jaxpr constvars, and literals).  Empty set
    for non-scan views."""
    if view.scan_num_consts is None:
        return set()
    jx = view.jaxpr
    inv = set(jx.invars[:view.scan_num_consts]) | set(jx.constvars)
    for eqn in jx.eqns:
        if all(isinstance(v, _Literal) or v in inv
               for v in eqn.invars):
            inv.update(eqn.outvars)
    return inv


def aval_of(var):
    return getattr(var, "aval", None)


def dtype_name(var) -> Optional[str]:
    aval = aval_of(var)
    dt = getattr(aval, "dtype", None)
    return None if dt is None else str(dt)


def num_elements(var) -> int:
    aval = aval_of(var)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n


def is_low_precision(name: Optional[str]) -> bool:
    return name in LOW_PRECISION


def is_float(name: Optional[str]) -> bool:
    return name is not None and (name.startswith("float")
                                 or name in LOW_PRECISION)


# ------------------------------ config ------------------------------

@dataclasses.dataclass
class LintConfig:
    """Pass thresholds.  Defaults are deliberately permissive — the
    linter gates on violations a reviewer would flag, not on style."""

    # declared policy compute dtype ("bfloat16"/"float16"/None=infer
    # from the GEMM population: >=50% low-precision dots => low region)
    compute_dtype: Optional[str] = None
    # mesh axes the program may legally reduce over (None = trust the
    # axes bound by the traced shard_maps alone)
    expected_axes: Optional[Sequence[str]] = None
    # DP103: reductions of at least this many summed elements must not
    # accumulate in a low-precision dtype
    reduction_threshold: int = 1 << 16
    # DP102: round trips on tensors below this size are the amp
    # policy's own norm scale/bias re-promotions (FP32_CLASS_OPS
    # contract) — by-design, not a hazard
    min_roundtrip_elems: int = 4096
    # DP104: outputs at least this large are treated as state buffers
    large_output_elems: int = 1 << 14
    # DN301: state args below this many bytes are too small to matter
    state_bytes_floor: int = 1 << 16


# ------------------------------ entry points ------------------------------

def trace_jaxpr(fn, args, *, axis_env=None):
    """The closed jaxpr of `fn(*args)` — args may be arrays or
    ShapeDtypeStructs; tracing never touches a device buffer."""
    return jax.make_jaxpr(fn, axis_env=list(axis_env or []))(*args)


def lint_program(fn=None, args=(), *, jaxpr=None, program: str = "program",
                 config: Optional[LintConfig] = None,
                 axis_env=None) -> List[Finding]:
    """Run the jaxpr passes (dtype-policy + collectives) over
    `fn(*args)` (or a pre-traced `jaxpr=`) and return the findings."""
    from apex_tpu.lint import collectives as _cl
    from apex_tpu.lint import dtype_policy as _dp

    cfg = config or LintConfig()
    if jaxpr is None:
        if fn is None:
            raise TypeError("lint_program needs fn+args or jaxpr=")
        jaxpr = trace_jaxpr(fn, args, axis_env=axis_env)
    base_axes = frozenset(str(a) for a, _ in (axis_env or []))
    views = collect_views(jaxpr, base_axes=base_axes)
    findings: List[Finding] = []
    findings += _dp.run(views, program=program, config=cfg)
    findings += _cl.run(views, program=program, config=cfg)
    return findings


def lint_step(step, args, *, program: str = "step",
              config: Optional[LintConfig] = None,
              arg_names: Optional[Sequence[str]] = None,
              donate_argnums: Optional[Sequence[int]] = None,
              compile_report=None) -> List[Finding]:
    """Lint a built train step: the jaxpr passes over the EXACT program
    the step runs, plus the donation pass over the builder metadata
    (`step.arg_names` / `step.donate_argnums` — `ddp.make_train_step`
    and `make_tp_dp_train_step` attach them) and, when a
    `CompileReport` (or its dict) is given, the static-vs-runtime
    donation cross-check."""
    from apex_tpu.lint import donation as _dn

    cfg = config or LintConfig()
    if cfg.expected_axes is None:
        mesh_axes = getattr(step, "mesh_axis_names", None)
        if mesh_axes:
            cfg = dataclasses.replace(
                cfg, expected_axes=tuple(str(a) for a in mesh_axes))
    # trace the step UNDERNEATH host-side wrappers (RecompileSentry
    # exposes `wrapped`): tracing a wrapper would run its bookkeeping
    # on tracer args — bumping call counts and pre-registering the
    # argument signature the sentry's compile-proxy relies on
    target = getattr(step, "wrapped", step)
    findings = lint_program(target, args, program=program, config=cfg)
    findings += _dn.run(
        step, args, program=program, config=cfg,
        arg_names=arg_names, donate_argnums=donate_argnums,
        compile_report=compile_report)
    return findings
