"""AOT compile audit: `analyze_step(step_fn, args) -> CompileReport`.

XLA already computes everything an operator needs to pick a batch size
— per-program argument/output/temp/alias bytes and generated-code size
(`compiled.memory_analysis()`), flops and bytes-accessed
(`compiled.cost_analysis()`) — at compile time, before a single step
executes.  This module lowers and compiles WITHOUT executing and folds
those numbers into one `CompileReport` that also answers the two
questions the raw analyses don't:

  * did donation actually take?  A donated input whose bytes do NOT
    show up as output aliasing means XLA kept a second copy alive —
    the "three fp32 state copies per step" failure bench.py's baseline
    works around by hand.  `donated_bytes` vs `alias_bytes` makes that
    a boolean (`donation_ok`), checked per program, not per anecdote.
  * does XLA's flop count agree with `monitor.flops`' analytic
    accounting?  Every MFU number the telemetry stack publishes divides
    by the analytic count; `flops_divergence` > `flops_tol` (default
    10%) flags the accounting before a wrong MFU lands in a table.

Everything degrades gracefully under `JAX_PLATFORMS=cpu` or an XLA
build that withholds an analysis: optional fields become None, nothing
raises.  The audit is pure AOT — it never touches the step's compiled
program or its numerics (the step is byte-identical whether or not it
was analyzed; tests/test_compile_report.py holds that line).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import numpy as np

# donated bytes may legitimately not alias in full: tiny non-donatable
# leaves (an i32 step counter whose output layout differs, scalar
# flags) ride inside big donated pytrees.  5% covers those without
# masking a real failure — a lost fp32 master copy is 1/3 of the state.
DONATION_TOL = 0.05


@dataclasses.dataclass
class CompileReport:
    """One compiled program's memory/cost anatomy (host-side, JSON-able
    via `to_dict`).  Fields from a backend analysis that is unavailable
    (CPU, older runtimes) are None — never fabricated.

    Bytes fields are per-device (what one chip's HBM sees).  `flops` /
    `bytes_accessed` are XLA cost-analysis totals; `analytic_flops` is
    the caller's `monitor.flops` accounting when given.  `budget` is
    the HBM budget table: traced per-argument bytes classified into
    params / optimizer_state / inputs (see `analyze_step`), plus the
    compiled program's output/temp/code terms.
    """

    backend: str
    device_kind: Optional[str]
    # memory_analysis()
    argument_bytes: Optional[int]
    output_bytes: Optional[int]
    temp_bytes: Optional[int]
    alias_bytes: Optional[int]
    generated_code_bytes: Optional[int]
    # cost_analysis()
    flops: Optional[float]
    bytes_accessed: Optional[float]
    # per top-level argument traced bytes, keyed by arg name
    arg_bytes: dict
    # donation verification
    donated_bytes: int
    undonated_bytes: Optional[int]
    donation_ok: Optional[bool]
    # flops cross-check vs monitor.flops analytic accounting
    analytic_flops: Optional[float]
    flops_divergence: Optional[float]
    flops_ok: Optional[bool]
    # HBM budget classification (params / optimizer_state / inputs /
    # activations_temps / outputs / generated_code)
    budget: dict
    # static-analysis attachment (ISSUE 6): analyze_step(..., lint=True)
    # runs apex_tpu.lint's program passes over the SAME step/args and
    # stores {"ok": bool, "findings": [Finding.to_dict(), ...]} here —
    # so the flight-recorder crash dump (which carries this report)
    # dies with the lint verdict alongside the HBM budget.  None when
    # linting was not requested.
    lint: Optional[dict] = None
    # comms attachment (ISSUE 7): analyze_step(..., comms=True) runs
    # monitor.comms' collective inventory + overlap analysis + ICI
    # roofline over the SAME compiled executable (no second compile)
    # and stores the CommsReport.to_dict() here — the crash dump then
    # carries the communication anatomy alongside the HBM budget, with
    # no recorder schema change (the field rides inside this report,
    # exactly like `lint`).  None when comms was not requested.
    comms: Optional[dict] = None

    def to_dict(self) -> dict:
        """Flat JSON-able dict (what the flight recorder attaches)."""
        return dataclasses.asdict(self)


def _leaf_bytes(leaf) -> int:
    """Traced size of one abstract/concrete array leaf."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def tree_bytes(tree) -> int:
    """Total bytes of a pytree of arrays / ShapeDtypeStructs."""
    return sum(_leaf_bytes(l) for l in jax.tree_util.tree_leaves(tree))


def _classify_budget(args: Sequence[Any], names: Sequence[str]) -> dict:
    """Split the traced argument bytes into the budget classes an
    operator reasons in.  Convention (the `make_train_step` arg order):
    an arg named `opt_state` with NamedTuple fields contributes its
    master buffer (`params`/`params_shard` fields) to "params" and the
    rest (moments, step counter) to "optimizer_state"; an arg whose
    name contains `kv_cache` or `page` is the serving path's paged KV
    pool (ISSUE 8 — the thing a serve report must price separately
    from weights: its size scales with CONCURRENT USERS, not model
    size); an arg named `params` is a bare weight pytree (the serve
    decode step passes weights without an optimizer wrapper); every
    other arg counts as "inputs" (batch, scaler, metrics pytree,
    timing rows)."""
    params = opt_state = inputs = kv_cache = 0
    for name, arg in zip(names, args):
        if name == "opt_state" and hasattr(arg, "_fields"):
            for field in arg._fields:
                b = tree_bytes(getattr(arg, field))
                if field in ("params", "params_shard"):
                    params += b
                else:
                    opt_state += b
        elif "kv_cache" in name or "page" in name:
            kv_cache += tree_bytes(arg)
        elif name == "params":
            params += tree_bytes(arg)
        else:
            inputs += tree_bytes(arg)
    return {"params": params, "optimizer_state": opt_state,
            "inputs": inputs, "kv_cache": kv_cache}


def _cost_entry(compiled) -> Optional[dict]:
    """The program's cost_analysis() dict, or None where the backend
    withholds it."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    return ca if isinstance(ca, dict) else None


def analyze_step(step_fn, args: Sequence[Any], *,
                 donated: Optional[Sequence[int]] = None,
                 arg_names: Optional[Sequence[str]] = None,
                 analytic_flops: Optional[float] = None,
                 flops_tol: float = 0.10,
                 donation_tol: float = DONATION_TOL,
                 lint: bool = False,
                 comms: bool = False) -> CompileReport:
    """Lower + compile `step_fn(*args)` WITHOUT executing and return
    the `CompileReport`.

    step_fn: anything with `.lower(*args)` — a jitted function, or the
    step `ddp.make_train_step` / `make_tp_dp_train_step` return (they
    attach a `.lower` that applies the same argument mapping as the
    call path).  args may be real arrays OR `jax.ShapeDtypeStruct`s —
    the audit never needs device buffers.

    donated: indices into `args` whose buffers the step donates.  None
    reads `step_fn.donate_argnums` (the builders attach it); pass ()
    to skip the donation check.  arg_names labels the budget table
    (None reads `step_fn.arg_names`, falling back to `arg{i}`).
    analytic_flops: the `monitor.flops` count for one step — the
    cross-check that validates every published MFU number.
    lint: also run `apex_tpu.lint`'s static program passes
    (dtype-policy, collectives, donation incl. the DN302 cross-check
    against THIS report's donation_ok) over the same step/args and
    attach the result as `report.lint` — so a crash dump carrying the
    report carries the lint verdict too.
    comms: also run `monitor.comms`' collective inventory + overlap
    analysis + ICI roofline over the SAME compiled executable (reused
    — no second XLA compile) and attach `CommsReport.to_dict()` as
    `report.comms` (ISSUE 7); replica groups map back to the step's
    `mesh_axis_names`/`mesh_axis_sizes` when the builder attached them.

    Whose time a profile of this program shows: `monitor.scopes.owners`
    over the executable's text maps every instruction to the scope of
    the program that owns it, and `scopes.step_owners()` does so for
    the step a process ran; docs/observability.md, "Time by owner",
    joins that with a `ProfileCapture`.
    """
    lower = getattr(step_fn, "lower", None)
    if lower is None:
        raise TypeError(
            f"{type(step_fn).__name__} has no .lower — pass a jitted "
            "function or a step built by ddp.make_train_step / "
            "make_tp_dp_train_step")
    if donated is None:
        donated = getattr(step_fn, "donate_argnums", ())
    if arg_names is None:
        arg_names = getattr(step_fn, "arg_names", None)
    names = list(arg_names) if arg_names is not None else []
    names += [f"arg{i}" for i in range(len(names), len(args))]
    names = names[:len(args)]

    compiled = lower(*args).compile()

    dev = jax.devices()[0]
    backend = jax.default_backend()
    device_kind = getattr(dev, "device_kind", None)

    arg_b = op_b = tmp_b = ali_b = code_b = None
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        arg_b = getattr(mem, "argument_size_in_bytes", None)
        op_b = getattr(mem, "output_size_in_bytes", None)
        tmp_b = getattr(mem, "temp_size_in_bytes", None)
        ali_b = getattr(mem, "alias_size_in_bytes", None)
        code_b = getattr(mem, "generated_code_size_in_bytes", None)

    cost = _cost_entry(compiled)
    xla_flops = cost.get("flops") if cost else None
    bytes_accessed = cost.get("bytes accessed") if cost else None

    per_arg = {nm: tree_bytes(a) for nm, a in zip(names, args)}
    donated_bytes = sum(tree_bytes(args[i]) for i in donated
                        if 0 <= i < len(args))
    undonated = donation_ok = None
    if donated_bytes and ali_b is not None:
        undonated = max(0, donated_bytes - int(ali_b))
        donation_ok = undonated <= donated_bytes * donation_tol
    elif not donated_bytes:
        undonated, donation_ok = 0, True

    divergence = flops_ok = None
    if analytic_flops and xla_flops:
        divergence = abs(float(xla_flops) - float(analytic_flops)) \
            / max(float(analytic_flops), 1.0)
        flops_ok = divergence <= flops_tol

    budget = _classify_budget(args, names)
    budget["activations_temps"] = tmp_b
    budget["outputs"] = op_b
    budget["generated_code"] = code_b

    report = CompileReport(
        backend=backend, device_kind=device_kind,
        argument_bytes=None if arg_b is None else int(arg_b),
        output_bytes=None if op_b is None else int(op_b),
        temp_bytes=None if tmp_b is None else int(tmp_b),
        alias_bytes=None if ali_b is None else int(ali_b),
        generated_code_bytes=None if code_b is None else int(code_b),
        flops=None if xla_flops is None else float(xla_flops),
        bytes_accessed=(None if bytes_accessed is None
                        else float(bytes_accessed)),
        arg_bytes=per_arg,
        donated_bytes=int(donated_bytes),
        undonated_bytes=undonated,
        donation_ok=donation_ok,
        analytic_flops=(None if analytic_flops is None
                        else float(analytic_flops)),
        flops_divergence=divergence,
        flops_ok=flops_ok,
        budget=budget,
    )
    if lint:
        # advisory, never fatal (the observatory's degradation
        # contract): a lint-side crash must not void the audit that
        # already succeeded — it becomes {"ok": None, "error": ...}
        try:
            from apex_tpu import lint as lint_lib
            findings = lint_lib.lint_step(
                step_fn, args, program="analyze_step",
                arg_names=names, donate_argnums=donated,
                compile_report=report)
            report.lint = {"ok": not findings,
                           "findings": [f.to_dict() for f in findings]}
        except Exception as e:
            report.lint = {"ok": None, "findings": [],
                           "error": repr(e)[:200]}
    if comms:
        # same degradation contract as lint: the comms plane is
        # advisory here — a parser-side surprise must not void the
        # memory/donation audit that already succeeded
        try:
            from apex_tpu.monitor import comms as comms_lib
            report.comms = comms_lib.comms_report(
                step_fn, args, compiled=compiled).to_dict()
        except Exception as e:
            report.comms = {"ok": None, "error": repr(e)[:200]}
    return report


def _human_bytes(b) -> str:
    if b is None:
        return "n/a"
    for unit, div in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if b >= div:
            return f"{b / div:.2f} {unit}"
    return f"{int(b)} B"


def render_budget_table(report) -> str:
    """The HBM budget table, the thing an operator reads before picking
    a batch size.  Accepts a CompileReport or its to_dict() (the crash
    dump attaches the dict form)."""
    r = report.to_dict() if hasattr(report, "to_dict") else dict(report)
    budget = r.get("budget") or {}
    lines = [
        "=== HBM budget ===",
        f"backend: {r.get('backend')}"
        + (f" ({r['device_kind']})" if r.get("device_kind") else ""),
        "| class               |       size |",
        "|---|---|",
    ]
    for key, label in (("params", "params (master)"),
                       ("optimizer_state", "optimizer state"),
                       ("kv_cache", "kv cache (pages)"),
                       ("inputs", "inputs (batch etc.)"),
                       ("activations_temps", "activations + temps"),
                       ("outputs", "outputs"),
                       ("generated_code", "generated code")):
        if key == "kv_cache" and not budget.get(key):
            continue          # training steps have no pool; keep tables tidy
        lines.append(f"| {label:<19} | "
                     f"{_human_bytes(budget.get(key)):>10} |")
    alias = r.get("alias_bytes")
    if alias is not None:
        lines.append(f"| aliased (donated)   | "
                     f"{_human_bytes(alias):>10} |")
    don = r.get("donation_ok")
    if don is False:
        lines.append(
            f"** DONATION FAILED: "
            f"{_human_bytes(r.get('undonated_bytes'))} of "
            f"{_human_bytes(r.get('donated_bytes'))} donated input NOT "
            "aliased — a second state copy is alive")
    elif don is True and r.get("donated_bytes"):
        lines.append("donation: ok (donated state aliases in place)")
    if r.get("flops_ok") is False:
        lines.append(
            f"** FLOPS ACCOUNTING DIVERGES: xla {r.get('flops'):.3e} vs "
            f"analytic {r.get('analytic_flops'):.3e} "
            f"({100 * r.get('flops_divergence'):.0f}% — MFU numbers "
            "derived from the analytic count are suspect)")
    elif r.get("flops_divergence") is not None:
        lines.append(
            f"flops: xla agrees with analytic accounting to "
            f"{100 * r['flops_divergence']:.1f}%")
    lint = r.get("lint")
    if lint is not None:
        if lint.get("ok"):
            lines.append("lint: clean (static program passes)")
        else:
            rules = sorted({f.get("rule", "?")
                            for f in lint.get("findings") or []})
            lines.append(
                f"** LINT: {len(lint.get('findings') or [])} "
                f"finding(s) [{', '.join(rules)}] — run "
                "scripts/lint_step.py for the full report")
    comms = r.get("comms")
    if comms is not None:
        if comms.get("collectives") is None:       # analyzer crashed
            lines.append(f"comms: unavailable "
                         f"({comms.get('error', '?')[:80]})")
        else:
            n = sum((comms.get("counts") or {}).values())
            total = comms.get("total_comm_bytes", 0)
            if comms.get("overlap_ok"):
                verdict = ("overlap ok" if comms.get("async_supported")
                           else "overlap n/a on this backend")
            else:
                n_ser = sum(1 for c in comms["collectives"]
                            if c.get("serialized"))
                verdict = f"** {n_ser} SERIALIZED"
            lines.append(
                f"comms: {n} collective(s), {_human_bytes(total)} — "
                f"{verdict} (render_comms_table for the full table)")
    return "\n".join(lines)
