"""Where set-up went: the program's own ledger of it, read once a run.

A program that keeps a set-up ledger
(`apex_tpu.monitor.compile.startup`) has, by the time the readers run,
a record of every program the process lowered, compiled or read from
the persistent cache (name, start, trace, lowering, compile or cache
read, hit or miss), the seconds each Pallas kernel's body took to
trace, its own spans of set-up work, and the bound on what came before
it was armed.  `scopes.step_kernels()` adds the static side: the
Pallas call sites of the step that ran and the equations in their
bodies.  `read` takes both once, keeps them on `observed`, and prints
them whole as a line of its own, as `lib/owners.py` prints the owners.

A program from before the ledger has no such module: `read` then
returns None, every reader built on it returns None, and the line
leaves those metrics out.
"""

from __future__ import annotations

import json
import time

TOP = 10                 # programs printed by seconds
STAGES = ("trace_s", "lower_s", "compile_s", "cache_read_s")


def read(observed: dict):
    """{"ledger", "step", "step_kernels", ...} of this run, or None
    where the program keeps no ledger.  `step` are the records, before
    steady state was marked, of the programs the step builder
    registered (`jit(local_step)`: the lowering and, where the loop's
    first call compiles again, that)."""
    if "setup_ledger" in observed:
        return observed["setup_ledger"]
    observed["setup_ledger"] = None
    try:
        from apex_tpu.monitor import scopes
        from apex_tpu.monitor.compile import startup
    except ImportError:
        return None
    seconds_of = startup.seconds_of
    t0 = time.perf_counter()
    ledger = startup.ledger()      # before the walk below adds to it
    names = scopes.registered()
    kernels = {}
    for name in names:
        for kernel, cell in scopes.step_kernels(name).items():
            mine = kernels.setdefault(kernel,
                                      {"call_sites": 0, "body_eqns": 0})
            for key in mine:
                mine[key] += cell[key]
    took = time.perf_counter() - t0
    jitted = {f"jit({name})" for name in names}
    setup = [r for r in ledger["programs"] if not r["steady"]]
    step = [r for r in setup if r["fun_name"] in jitted]
    traced = {}       # kernel -> its spans under the step's trace
    for name in names:
        for kernel, cell in ledger["kernels_by_program"].get(
                name, {}).items():
            mine = traced.setdefault(
                kernel, {"calls": 0, "trace_s": 0.0, "max_s": 0.0})
            mine["calls"] += cell["calls"]
            mine["trace_s"] += cell["trace_s"]
            mine["max_s"] = max(mine["max_s"], cell["max_s"])
    totals = ledger["totals"]["setup"]
    found = observed["setup_ledger"] = {
        "ledger": ledger, "step": step, "step_kernels": kernels,
        "step_kernel_spans": traced,
        "setup_s": sum(totals[k] for k in STAGES),
        "step_s": sum(seconds_of(r) for r in step)}
    by_seconds = sorted(setup, key=seconds_of, reverse=True)
    hits = sum(t["cache_hits"] for t in ledger["totals"].values())
    misses = sum(t["cache_misses"] for t in ledger["totals"].values())
    print(json.dumps({
        "phase": "setup_ledger", "read_s": took,
        "clock": ledger["clock"], "armed_at_s": ledger["armed_at_s"],
        "steady_at_s": ledger["steady_at_s"],
        "events": ledger["events"],
        "events_at_steady": ledger["events_at_steady"],
        "first_after_steady": ledger["first_after_steady"],
        "cache_hits": hits, "cache_misses": misses,
        "totals": ledger["totals"],
        "programs_dropped": ledger["programs_dropped"],
        "step": step,
        "programs_top": [
            {k: r.get(k) for k in ("fun_name", "at_s", *STAGES, "cache",
                                   "saved_s", "span", "inside")
             if r.get(k) is not None} for r in by_seconds[:TOP]],
        "programs_rest_s": sum(seconds_of(r) for r in by_seconds[TOP:]),
        "missed": [r["fun_name"] for r in ledger["programs"]
                   if r["cache"] == "miss"],
        "steady_programs": [r for r in ledger["programs"] if r["steady"]],
        "kernels": {
            kernel: {**traced.get(kernel, {}), **kernels.get(kernel, {})}
            for kernel in sorted({*traced, *kernels})},
        "kernels_all_programs": ledger["kernels"],
        "spans": ledger["spans"]}), flush=True)
    return found


def step_seconds(observed: dict, stage: str):
    """Seconds of one stage over the registered step's records before
    steady state; None where `read` is, or nothing was registered."""
    found = read(observed)
    if found is None or not found["step"]:
        return None
    return sum(r.get(stage) or 0.0 for r in found["step"])


def setup_total(observed: dict, key: str):
    """One of the ledger's exact totals before steady state."""
    found = read(observed)
    return None if found is None else found["ledger"]["totals"]["setup"][key]


def kernel_sum(observed: dict, key: str):
    """`call_sites` or `body_eqns` of `scopes.step_kernels()`, summed
    over the kernels; None where `read` is."""
    found = read(observed)
    if found is None:
        return None
    return sum(cell[key] for cell in found["step_kernels"].values())
