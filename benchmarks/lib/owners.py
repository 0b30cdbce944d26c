"""Device time by owner: the trace's seconds by instruction joined
with the program's own account of who owns each instruction.

`lib/trace.py` already gives, for the first device over the steady
window, the seconds each instruction of the step ran
(`observed["trace"]["first"]["ops"]`).  A program that names its step
from inside (`apex_tpu.monitor.scopes`: a `jax.named_scope` per
sublayer and phase, a `name=` per Pallas kernel) hands out, for the
step it ran, {instruction: (owner, direction, opcode)}.  Instruction
names are the same in both, so the join is a lookup.

A program from before the scopes has no such module: `table` then
returns None, every reader built on it returns None, and the line
leaves those metrics out.
"""

from __future__ import annotations

import collections
import json
import re
import time

from benchmarks.lib import trace

KINDS = ("kernel", "fusion", "collective", "copy")
UNOWNED = "unowned"
_BLOCK = re.compile(r"^block\d+")
# one instruction the trace saw: `ms` is milliseconds a step
Row = collections.namedtuple("Row", "name owner direction kind ms")


def kind_of(name: str, opcode: str, kernels) -> str:
    """One of KINDS: a named Pallas kernel, a collective, an XLA fusion
    (or a GEMM left outside one), or what only moves data: copies,
    slices, bitcasts, converts, layout custom calls."""
    if name.split(".")[0] in kernels:
        return "kernel"
    if trace.is_collective(name, opcode):
        return "collective"
    if opcode in ("fusion", "convolution", "dot"):
        return "fusion"
    return "copy"


def join(ops: dict, n_steps: int, owner_map: dict, kernels) -> list:
    """A Row for every instruction the trace saw.  One the program's
    text does not hold is unowned."""
    rows = []
    for name, seconds in ops.items():
        owner, direction, opcode = owner_map.get(
            name, (UNOWNED, "step", ""))
        rows.append(Row(name, owner, direction,
                        kind_of(name, opcode, kernels),
                        1e3 * seconds / n_steps))
    return rows


def summary(rows) -> dict:
    """{owner: {direction: {kind: ms a step}}}, the layers of a stack
    summed into `block*`."""
    out = {}
    for row in rows:
        cell = out.setdefault(_BLOCK.sub("block*", row.owner), {}).setdefault(
            row.direction, {})
        cell[row.kind] = cell.get(row.kind, 0.0) + row.ms
    return out


def table(observed: dict):
    """The joined rows of this run, or None where there is no trace or
    the program does not name its step.  Computed once a run and kept
    on `observed`; prints the whole table as a line of its own."""
    if "owners" in observed:
        return observed["owners"]
    observed["owners"] = None
    reduced = observed.get("trace")
    try:
        from apex_tpu.monitor import scopes
    except ImportError:
        return None
    if not reduced:
        return None
    import jax

    def reserved():
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("bytes_reserved")

    before, t0 = reserved(), time.perf_counter()
    owner_map = scopes.step_owners()
    took = time.perf_counter() - t0
    first = reduced["first"]
    rows = join(first["ops"], first["n_steps"], owner_map, scopes.KERNELS)
    observed["owners"] = rows
    unowned = sorted((r for r in rows if r.owner == UNOWNED),
                     key=lambda r: -r.ms)
    print(json.dumps({
        "phase": "owners", "step_owners_s": took,
        "bytes_reserved_before": before, "bytes_reserved_after": reserved(),
        "instructions": len(rows),
        "busy_ms_per_step": 1e3 * first["busy_s"] / first["n_steps"],
        "owned_ms_per_step": sum(r.ms for r in rows),
        "ms_per_step": summary(rows),
        "unowned_top": [[r.name, r.kind, r.ms] for r in unowned[:10]]}),
        flush=True)
    return rows


def ms(observed: dict, *, owner=None, direction=None, name=None,
       but_name=None):
    """Milliseconds a step, first device, of the instructions whose
    owner, direction and instruction name match the given patterns
    (regular expressions, matched from the start) and whose name does
    not match `but_name`; None where `table` is."""
    rows = table(observed)
    if rows is None:
        return None

    def matches(pattern, text):
        return pattern is None or re.match(pattern, text) is not None

    return sum(r.ms for r in rows
               if matches(owner, r.owner) and matches(direction, r.direction)
               and matches(name, r.name)
               and (but_name is None or not re.match(but_name, r.name)))
