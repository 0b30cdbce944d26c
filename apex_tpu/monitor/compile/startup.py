"""`startup` — the set-up ledger: where the seconds before the first
useful step went, by stage, program and kernel.

Steady state names itself from inside (`monitor.scopes`); set-up did
not.  A process that trains spends its first half minute (warm) or
several minutes (cold) tracing, lowering, compiling or reading from
the persistent cache some dozens of programs, of which the step is
one, and nothing in the program said which took what.  JAX does: every
trace, every jaxpr-to-MLIR lowering and every backend compile is
published through `jax.monitoring` with the function's name, when it
starts (a scalar event) and when it ends (a duration and a time span),
and the persistent cache publishes its requests, hits, misses, read
times and the compile seconds a hit saved.  The ledger listens to
those and keeps, in memory and bounded:

  * a **program record** for every program lowered, compiled or read
    from the cache, in order (`ledger()["programs"]`);
  * **spans** where the program does set-up work of its own (`span`:
    the mesh, the optimizer's state, the step's build, the tuner's
    tables, `scopes.step_text`), each also a
    `jax.profiler.TraceAnnotation("apex.setup/<name>")`, so that a
    profile taken over start-up shows them on the trace's own clock;
  * **kernel spans** (`kernel_span`, a `with` around every statement
    of `apex_tpu/ops` that *binds* a `pl.pallas_call`, where its body
    is traced): `{kernel: {"calls": n, "trace_s": s, "max_s": longest}}`
    (a memoised call pays its body once: `max_s` near `trace_s`).  JAX
    cannot say which kernel body its tracing time went to; this can;
  * the process's start as the OS recorded it and the moment the
    ledger was armed: what lies before (Python, JAX's import, the TPU
    runtime's start) can only be bounded from inside.

Nested spans: the jitted helpers of `jax.numpy` fire one trace event
each while a program is traced (thousands a step).  Those are counted
(`nested_traces`) and not recorded, and their time stays inside the
program that caused them.  A program that is lowered or compiled
*inside* another's span (an eager `jnp` call on concrete values while
a model is traced) is recorded, names the span it ran in (`inside`),
and its seconds are taken off that span: a record's seconds are self
time, so the records sum to the wall time the stages covered.

Arming is explicit and idempotent (`arm()`): `initialize_model_parallel`
and `scopes.register` call it, `import apex_tpu` does not.  `disarm()`
takes the listeners off again and leaves every other listener of
`jax.monitoring` where it was.  Nothing here is on the per-step path:
a cached `jit` dispatch fires no monitoring event and a kernel span
runs only while Python traces.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE = "/jax/compilation_cache/"
_CACHE_COUNTS = {_CACHE + "compile_requests_use_cache": "cache_requests",
                 _CACHE + "cache_hits": "cache_hits",
                 _CACHE + "cache_misses": "cache_misses"}
_CACHE_SECONDS = {_CACHE + "compile_time_saved_sec": "saved_s",
                  _CACHE + "cache_retrieval_time_sec": "retrieval_s"}
_SECONDS = ("trace_s", "lower_s", "compile_s", "cache_read_s")
MAX_PROGRAMS = 512      # records kept; the totals stay exact beyond
MAX_SPANS = 256
MAX_ROOTS = 64          # programs whose kernel spans are kept apart
MAX_AWAITING = 64       # lowered and not compiled yet, by name
EAGER = "(eager)"       # a kernel bound outside any trace


@contextlib.contextmanager
def kernel_span(name: str):
    """`with kernel_span("flash_fwd"): out = call(*args)` around the
    statement that *binds* a `pl.pallas_call`, where its body is
    traced: tracing the body is what binding costs, and a memoised call
    whose jaxpr JAX still holds costs nothing.  One line a binding.

    A `with`, not a wrapper around the callable: on the chip's host a
    Python function between an op and its `pallas_call`, even one that
    only passes its arguments on, made the tracing under it a tenth
    slower (PERF.md, PR 36); a `with` puts no frame on the stack."""
    start = time.perf_counter()
    try:
        yield
    finally:
        _LEDGER.kernel_traced(name, time.perf_counter() - start)


def process_started_at():
    """The process's start in `time.time()` seconds, as the OS has it
    (`/proc/self/stat`'s start time against `/proc/uptime`, to a clock
    tick); None where there is no such record."""
    try:
        with open("/proc/self/stat") as f:
            after_comm = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        ticks = os.sysconf("SC_CLK_TCK")
        return time.time() - (uptime - int(after_comm[19]) / ticks)
    except (OSError, ValueError, IndexError):
        return None


def seconds_of(record: dict) -> float:
    """What a program record cost: trace, lowering, and the compile or
    the read of the cache."""
    return sum(record.get(k) or 0.0 for k in _SECONDS)


def _never_raises(listener):
    """A listener runs inside JAX's compile path: a fault of the
    instrument must cost a count, not the program's compile."""
    @functools.wraps(listener)
    def guarded(self, *args, **kwargs):
        try:
            listener(self, *args, **kwargs)
        except Exception as e:  # noqa: BLE001 — the boundary that keeps running
            self.faults += 1
            self.first_fault = self.first_fault or repr(e)

    return guarded


class _Open:
    """A stage span that has started and not ended, on one thread."""

    __slots__ = ("stage", "name", "inner_s", "pending", "cache")

    def __init__(self, stage, name):
        self.stage = stage
        self.name = name
        self.inner_s = 0.0     # seconds of programs recorded inside
        self.pending = None    # the last trace that closed in here and
        #                        that no lowering has claimed:
        #                        (fun_name, start, self seconds)
        self.cache = None      # what the cache said, for a compile


def _new_totals() -> dict:
    return {"programs": 0, "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_read_s": 0.0, "saved_s": 0.0, "cache_requests": 0,
            "cache_hits": 0, "cache_misses": 0, "nested_traces": 0,
            "traces_without_program": 0, "traces_without_program_s": 0.0}


class SetupLedger:
    """The account of one process.  `arm()`/`ledger()` of this module
    serve the process-wide one; a test makes its own."""

    def __init__(self, max_programs: int = MAX_PROGRAMS,
                 max_spans: int = MAX_SPANS):
        self.max_programs = max_programs
        self.max_spans = max_spans
        self.armed = False
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        self.process_started_at = process_started_at()
        self.armed_at = None
        self.steady_at = None
        self.events = 0             # every callback and span that fired
        self.faults = 0             # listeners that raised (and were held)
        self.first_fault = None
        self.events_at_steady = None
        self.first_after_steady = None   # (time, what) of the first one
        self.programs = []
        self.dropped = 0
        self.totals = {"setup": _new_totals(), "steady": _new_totals()}
        self.kernels = {}           # root program -> kernel -> its cell
        self.spans = []
        self.spans_dropped = 0
        self.n_programs = 0         # records made, kept or not
        self._awaiting = {}         # lowered, not compiled: name -> record
        self._local = threading.local()

    # ------------------------------ arming ------------------------------

    def arm(self) -> bool:
        """Start listening; True where this call did it."""
        import jax

        with self._lock:
            if self.armed:
                return False
            self.armed = True
            if self.armed_at is None:
                self.armed_at = time.time()
        m = jax.monitoring
        m.register_scalar_listener(self._on_enter)
        m.register_event_time_span_listener(self._on_span)
        m.register_event_duration_secs_listener(self._on_seconds)
        m.register_event_listener(self._on_event)
        return True

    def disarm(self) -> None:
        """Stop listening.  Only this ledger's own listeners go: JAX's
        `clear_event_listeners` would take everybody's."""
        import jax

        with self._lock:
            if not self.armed:
                return
            self.armed = False
        m = jax.monitoring
        m.unregister_scalar_listener(self._on_enter)
        m.unregister_event_time_span_listener(self._on_span)
        m.unregister_event_duration_listener(self._on_seconds)
        m.unregister_event_listener(self._on_event)

    def mark_steady(self) -> None:
        """Set-up is over: a program recorded from here on is
        `steady: true` (`RecompileSentry.mark_steady` calls this)."""
        if self.steady_at is None:
            self.steady_at = time.time()
            self.events_at_steady = self.events

    # ----------------------------- listeners -----------------------------

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = [_Open("root", None)]
            self._local.spans = []
            return self._local.stack

    def _phase(self) -> dict:
        return self.totals["steady" if self.steady_at is not None
                           else "setup"]

    def _fired(self, kind: str, name) -> None:
        self.events += 1
        if self.steady_at is not None and self.first_after_steady is None:
            self.first_after_steady = (time.time(), f"{kind} {name}")

    @_never_raises
    def _on_enter(self, event, value, fun_name=None, **_):
        stage = _STAGES.get(event)
        if stage is None:
            return
        self._fired(stage, fun_name)
        self._stack().append(_Open(stage, fun_name))

    @_never_raises
    def _on_span(self, event, start, end, fun_name=None, **_):
        stage = _STAGES.get(event)
        if stage is None:
            return
        self._fired(stage, fun_name)
        stack = self._stack()
        me = None
        for i in range(len(stack) - 1, 0, -1):
            if stack[i].stage == stage and stack[i].name == fun_name:
                me = stack[i]
                del stack[i:]       # and what never closed above it
                break
        if me is None:              # armed while this span was open
            me = _Open(stage, fun_name)
        parent = stack[-1]
        self._unclaimed(me, nested=True)
        self_s = max(0.0, end - start - me.inner_s)
        parent.inner_s += me.inner_s
        if stage == "trace":
            # a helper of jax.numpy, unless a lowering claims it next
            self._unclaimed(parent, nested=len(stack) > 1)
            parent.pending = (fun_name, start, self_s)
            return
        parent.inner_s += self_s
        if stage == "lower":
            record = self._record(fun_name, start, parent)
            traced = parent.pending
            if traced is not None and fun_name.endswith(f"({traced[0]})"):
                parent.pending = None
                parent.inner_s += traced[2]
                record["at_s"] = self._at(traced[1])
                self._add(record, "trace_s", traced[2])
            self._add(record, "lower_s", self_s)
            self._awaiting[fun_name] = record
            while len(self._awaiting) > MAX_AWAITING:
                self._awaiting.pop(next(iter(self._awaiting)))
            return
        # the executable of the lowering before it, or (lowering served
        # from JAX's cache, as after `.lower()`) a record of its own
        record = (self._awaiting.pop(fun_name, None)
                  or self._record(fun_name, start, parent))
        said = me.cache or {}
        if said.get("cache_hits"):
            record["cache"] = "hit"
            self._add(record, "cache_read_s", self_s)
            self._add(record, "saved_s", said.get("saved_s", 0.0))
        else:
            record["cache"] = "miss" if said.get("cache_misses") else "unused"
            self._add(record, "compile_s", self_s)

    def _compiling(self):
        """The backend compile open on this thread, or None."""
        top = self._stack()[-1]
        return top if top.stage == "compile" else None

    @_never_raises
    def _on_seconds(self, event, seconds, **_):
        key = _CACHE_SECONDS.get(event)
        if key is None:
            return
        self._fired("cache", key)
        me = self._compiling()
        if me is not None:
            me.cache = me.cache or {}
            me.cache[key] = me.cache.get(key, 0.0) + seconds

    @_never_raises
    def _on_event(self, event, **_):
        key = _CACHE_COUNTS.get(event)
        if key is None:
            return
        self._fired("cache", key)
        self._phase()[key] += 1     # exact, whoever compiles
        me = self._compiling()
        if me is not None:
            me.cache = me.cache or {}
            me.cache[key] = me.cache.get(key, 0) + 1

    # ------------------------------ records ------------------------------

    def _at(self, when: float) -> float:
        return when - (self.process_started_at or self.armed_at or when)

    def _unclaimed(self, entry: _Open, nested: bool) -> None:
        """The trace that closed inside `entry` and that nothing
        lowered: a helper's (its time stays in the span around it), or
        at the top a trace for its own sake (`eval_shape`, `.trace()`,
        a jaxpr JAX's cache served)."""
        if entry.pending is None:
            return
        totals = self._phase()
        if nested:
            totals["nested_traces"] += 1
        else:
            totals["traces_without_program"] += 1
            totals["traces_without_program_s"] += entry.pending[2]
        entry.pending = None

    def _record(self, fun_name, start, parent) -> dict:
        steady = self.steady_at is not None
        self.n_programs += 1
        record = {"n": self.n_programs, "fun_name": fun_name,
                  "at_s": self._at(start), "cache": "unused",
                  "steady": steady}
        if parent.name is not None:
            record["inside"] = parent.name
        spans = self._local.spans
        if spans:
            record["span"] = spans[-1]
        self._phase()["programs"] += 1
        if len(self.programs) >= self.max_programs:
            # the least seconds go first: the step and whatever was
            # slow stay, whenever they came
            least = min(range(len(self.programs)),
                        key=lambda i: seconds_of(self.programs[i]))
            del self.programs[least]
            self.dropped += 1
        self.programs.append(record)
        return record

    def _add(self, record, key, seconds) -> None:
        record[key] = record.get(key, 0.0) + seconds
        self.totals["steady" if record["steady"] else "setup"][key] += seconds

    def heaviest_since(self, n: int):
        """The program record with the most seconds among those
        numbered above `n` (a copy); None where there is none or nobody
        is listening.  `self.n_programs` before a call and this after
        it name the program that call compiled."""
        if not self.armed:
            return None
        since = [r for r in self.programs if r["n"] > n]
        return dict(max(since, key=seconds_of)) if since else None

    # ------------------------------- spans -------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        """Set-up work of the program's own: a record with name, start,
        seconds and parent, and the annotation `apex.setup/<name>` in a
        profile that is being taken."""
        import jax

        stack = self._stack()
        spans = self._local.spans
        parent = spans[-1] if spans else None
        spans.append(name)
        self._fired("span", name)
        start = time.time()
        try:
            with jax.profiler.TraceAnnotation(f"apex.setup/{name}"):
                yield
        finally:
            spans.pop()
            if len(self.spans) < self.max_spans:
                self.spans.append({
                    "name": name, "at_s": self._at(start),
                    "s": time.time() - start, "parent": parent,
                    "inside": stack[1].name if len(stack) > 1 else None,
                    "steady": self.steady_at is not None})
            else:
                self.spans_dropped += 1

    def kernel_traced(self, kernel: str, seconds: float) -> None:
        """One bound `pallas_call` of `kernel`, filed under the program
        whose trace is the outermost open on this thread."""
        self._fired("kernel", kernel)
        stack = self._stack()
        root = stack[1].name if len(stack) > 1 else EAGER
        if root not in self.kernels and len(self.kernels) >= MAX_ROOTS:
            root = "(other)"
        cell = self.kernels.setdefault(root, {}).setdefault(
            kernel, {"calls": 0, "trace_s": 0.0, "max_s": 0.0})
        cell["calls"] += 1
        cell["trace_s"] += seconds
        cell["max_s"] = max(cell["max_s"], seconds)

    # ------------------------------ reading ------------------------------

    def ledger(self) -> dict:
        """The account so far, JSON-able.  Seconds are self time; `at_s`
        counts from the process's start (or from arming where the OS
        gives none: `clock` says which)."""
        by_kernel = {}
        for kernels in self.kernels.values():
            for kernel, one in kernels.items():
                cell = by_kernel.setdefault(
                    kernel, {"calls": 0, "trace_s": 0.0, "max_s": 0.0})
                cell["calls"] += one["calls"]
                cell["trace_s"] += one["trace_s"]
                cell["max_s"] = max(cell["max_s"], one["max_s"])
        first = self.first_after_steady
        return {
            "clock": ("process_start" if self.process_started_at is not None
                      else "armed"),
            "process_started_at": self.process_started_at,
            "armed": self.armed,
            # the span before the program, bounded from inside: Python,
            # JAX's import, the runtime's start, the caller's own work
            "armed_at_s": (None if self.armed_at is None
                           else self._at(self.armed_at)),
            "steady_at_s": (None if self.steady_at is None
                            else self._at(self.steady_at)),
            "events": self.events,
            "faults": self.faults,
            "first_fault": self.first_fault,
            "events_at_steady": self.events_at_steady,
            "first_after_steady": (None if first is None else
                                   {"at_s": self._at(first[0]),
                                    "what": first[1]}),
            "programs": [dict(r) for r in self.programs],
            "programs_dropped": self.dropped,
            "totals": {k: dict(v) for k, v in self.totals.items()},
            "kernels": by_kernel,
            "kernels_by_program": {
                root: {k: dict(one) for k, one in kernels.items()}
                for root, kernels in self.kernels.items()},
            "spans": [dict(s) for s in self.spans],
            "spans_dropped": self.spans_dropped,
        }


_LEDGER = SetupLedger()


# the process's ledger under the module's name (tests reset it in place)
arm = _LEDGER.arm
disarm = _LEDGER.disarm
mark_steady = _LEDGER.mark_steady
ledger = _LEDGER.ledger
heaviest_since = _LEDGER.heaviest_since
span = _LEDGER.span


def n_programs() -> int:
    """Program records the process's ledger has made so far."""
    return _LEDGER.n_programs
