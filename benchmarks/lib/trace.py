"""From a profiler trace to numbers: the one reduction every PR shares.

`read_xplane` turns the `.xplane.pb` the JAX profiler writes into plain
events; `reduce` turns events into busy and idle time, the operation
table, exposed collective time and the idle gaps by what the host was
doing.  The two are apart so that a small recorded trace, kept as JSON
under `benchmarks/tests/data/`, holds `reduce` to its numbers.

What a TPU v5e trace looks like (read by hand, PR 24): one plane a chip,
`/device:TPU:<n>`; on it the line "XLA Modules" has one event for each
execution of a jitted program (`jit_local_step(<fingerprint>)`) and the
line "XLA Ops" one event for each HLO instruction it ran, one at a time
on the core, named by the instruction's whole text (`%fusion.123 =
bf16[...] fusion(...), kind=kOutput, ...`): `read_xplane` keeps the
name before ` = ` and, apart, a short label (opcode and result type).
"Async XLA Ops" holds the copies that run beside the core and "Steps"
repeats the modules; neither is read.  The host's threads are lines of
the plane `/host:CPU`, on the device's clock to within a millisecond;
the benchmark's own spans (`jax.profiler.TraceAnnotation`) are events of
the line `python3`.
"""

from __future__ import annotations

import gzip
import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")
ELSEWHERE = "elsewhere"
_INSTRUCTION = re.compile(
    r"^%?(?P<name>\S+) = (?P<type>.+?) (?P<opcode>[a-z][\w-]*)\(")


def short(text: str):
    """(name, label) of an "XLA Ops" event: the instruction's name, and
    its opcode with the start of its result type for a reader."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return text.lstrip("%"), ""
    return m.group("name"), f"{m.group('opcode')} {m.group('type')[:48]}"


def read_xplane(path: str) -> dict:
    """{"devices": {"<n>": {"ops": [[name, start_ns, dur_ns], ...],
    "modules": [...]}}, "host": [[name, start_ns, dur_ns], ...],
    "labels": {op name: label}, "lines": {plane: {line: n_events}}}
    from an .xplane.pb file.  Of the host's events only the benchmark's
    own spans are kept."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "labels": {}, "lines": {}}
    for plane in data.planes:
        counts = out["lines"].setdefault(plane.name, {})
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            counts[line.name] = counts.get(line.name, 0) + len(events)
            if device and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                if key == "ops":
                    for event in events:
                        event[0], label = short(event[0])
                        out["labels"].setdefault(event[0], label)
                out["devices"].setdefault(
                    device.group(1), {"ops": [], "modules": []}
                )[key].extend(events)
            elif plane.name == HOST_PLANE:
                out["host"].extend(
                    e for e in events if e[0].startswith(SPAN_PREFIX))
    for dev in out["devices"].values():
        dev["ops"].sort(key=lambda e: e[1])
        dev["modules"].sort(key=lambda e: e[1])
    out["host"].sort(key=lambda e: e[1])
    return out


def save_events(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f, separators=(",", ":"))


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals) -> list:
    """Sorted, disjoint [start, end) pairs covering `intervals`."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        elif end > start:
            out.append([start, end])
    return out


def total(intervals) -> int:
    return sum(end - start for start, end in intervals)


def subtract(a, b) -> list:
    """The parts of the disjoint sorted intervals `a` outside `b`."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, at = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append([at, b[k][0]])
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append([at, end])
    return out


def clip(events, window) -> list:
    """[name, start, end] of every event, cut to `window`."""
    lo, hi = window
    return [[name, max(start, lo), min(start + dur, hi)]
            for name, start, dur in events
            if start < hi and start + dur > lo]


def step_program(modules) -> str:
    """The program that took most device time: the train step."""
    seconds = {}
    for name, _, dur in modules:
        seconds[name] = seconds.get(name, 0) + dur
    return max(seconds, key=seconds.get)


def steady_window(modules):
    """(window, n_steps): from the start of the step program's second
    traced execution to the end of its last.  The first began on an
    empty queue, so the gap before it is the tracer's, not the loop's."""
    name = step_program(modules)
    runs = [(start, start + dur) for n, start, dur in modules if n == name]
    if len(runs) < 3:
        raise ValueError(
            f"{len(runs)} traced execution(s) of {name}: too few for a "
            "steady window")
    return (runs[1][0], runs[-1][1]), len(runs) - 1


def is_collective(name: str, label: str = "") -> bool:
    """By the instruction's name or by its opcode: an async collective
    is `<kind>-start` / `<kind>-done`, under its own opcode or wrapped
    in `async-start` / `async-done` and then known by its name only."""
    return bool(COLLECTIVE.match(name)
                or COLLECTIVE.match(label.split(" ", 1)[0]))


def reduce(events: dict) -> dict:
    """The numbers the per-layer metrics and the breakdown read.

    Per device: `busy_s`, the union of its operations' intervals inside
    the steady window; `idle_pct`; `exposed_collective_s`, the time a
    collective ran and no other operation did.  For the first device
    also `ops`, seconds by instruction name, and `idle_gaps`, idle
    seconds by the benchmark span the host was in; it is `first`.  All
    over the steady window of `n_steps` steps, `window_s` long."""
    labels = events.get("labels", {})

    def collective(name):
        return is_collective(name, labels.get(name, ""))

    devices = {}
    for dev_id, dev in sorted(events["devices"].items(),
                              key=lambda kv: int(kv[0])):
        window, n_steps = steady_window(dev["modules"])
        ops = clip(dev["ops"], window)
        busy = union((s, e) for _, s, e in ops)
        comm = union((s, e) for n, s, e in ops if collective(n))
        compute = union((s, e) for n, s, e in ops if not collective(n))
        span = window[1] - window[0]
        devices[dev_id] = {
            "n_steps": n_steps, "window_s": span / 1e9,
            "busy_s": total(busy) / 1e9,
            "idle_pct": 100.0 * (1.0 - total(busy) / span),
            "exposed_collective_s": total(subtract(comm, compute)) / 1e9}
        if len(devices) == 1:
            by_name = {}
            for name, start, end in ops:
                by_name[name] = by_name.get(name, 0) + (end - start) / 1e9
            devices[dev_id]["ops"] = by_name
            devices[dev_id]["idle_gaps"] = _gaps_by_span(
                subtract([list(window)], busy), events["host"])
    first = next(iter(devices.values()))
    return {"devices": devices, "first": first,
            "n_steps": first["n_steps"], "window_s": first["window_s"],
            "busy_s": sum(d["busy_s"] for d in devices.values())
            / len(devices),
            "idle_pct": max(d["idle_pct"] for d in devices.values())}


def _gaps_by_span(gaps, host_events) -> dict:
    """Idle seconds by the span the host was in while the device idled."""
    out = {}
    covered = []
    for name in sorted({e[0] for e in host_events}):
        spans = union((s, s + d) for n, s, d in host_events if n == name)
        inside = subtract(gaps, subtract(gaps, spans))
        out[name] = total(inside) / 1e9
        covered.extend(spans)
    out[ELSEWHERE] = total(subtract(gaps, union(covered))) / 1e9
    return out


def kernel_seconds_per_step(observed: dict, kind: str):
    """First-device seconds a step in the kernels the job filed under
    `kind` (`observed["kernels"]`, instruction names); None where there
    is no trace, no such kernel, or no event of it."""
    reduced, names = observed.get("trace"), observed["kernels"].get(kind)
    if not (reduced and names):
        return None
    names = set(names)
    first = reduced["first"]
    return sum(s for n, s in first["ops"].items()
               if n in names) / first["n_steps"] or None


def by_label(ops: dict, labels: dict) -> dict:
    """Seconds by label: the instructions that share an opcode and a
    result type (a layer's GEMM in each of 24 layers, a kernel's 24
    calls) are one row, "<label> x<how many>", for a reader."""
    seconds, count = {}, {}
    for name, s in ops.items():
        label = labels.get(name) or name
        seconds[label] = seconds.get(label, 0) + s
        count[label] = count.get(label, 0) + 1
    return {f"{label} x{count[label]}": s for label, s in seconds.items()}


def top(table: dict, n: int = 10) -> list:
    """[[name, seconds], ...], the `n` largest."""
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
