"""Profiler capture scoped to a step window.

`profile_capture(range(10, 13), logdir=...)` arms a `jax.profiler`
trace that starts when the first step of the window begins and stops
after its last step — the usual "skip compile, grab 3 steady-state
steps" workflow, without littering the training loop with
start/stop_trace calls:

    cap = monitor.profile_capture(range(3, 6), logdir="/tmp/trace")
    for i in range(steps):
        with cap.step(i):
            state, ... = train_step(...)
    cap.close()   # safety net if the loop exits early
    report = monitor.analyze_trace(cap.trace_path())  # ISSUE 15

Each captured step is wrapped in a trace annotation (default name
"train-step"); phase timers used inside the step already emit
`TraceAnnotation`s with their own `_Timer` names (utils/timers.py), so
the profile shows the same names `Timers.log` prints.  After the
window closed, `trace_path()` resolves the `trace.json.gz` the
profiler wrote so `monitor.timeline.analyze_trace` can turn the
capture into a measured step anatomy without the caller spelunking
`logdir/plugins/profile/…` by hand.
"""

from __future__ import annotations

import contextlib
from typing import Iterable, Optional

import jax


class ProfileStepReentryError(RuntimeError):
    """`ProfileCapture.step(i)` was entered while a previous `step()`
    context was still open.  Nested step scopes would nest the trace
    annotations and make every "step" in the resulting trace the hull
    of its children — the capture contract is one scope per training
    step, entered sequentially."""


class ProfileCapture:
    def __init__(self, step_range: Iterable[int], *,
                 logdir: str = "/tmp/apex_tpu_trace",
                 annotation: str = "train-step"):
        steps = sorted(set(int(s) for s in step_range))
        # one capture = ONE contiguous trace window [first, last] —
        # start_trace fires entering `first`, stop_trace after `last`.
        # A gapped range (e.g. {3, 10}) used to be silently treated as
        # its hull, capturing steps the caller never asked for; honor
        # the contract by refusing it instead (two windows = two
        # ProfileCapture objects)
        if steps and steps[-1] - steps[0] != len(steps) - 1:
            raise ValueError(
                f"profile step_range must be contiguous, got {steps}; "
                "a capture arms a single [first, last] trace window — "
                "use one ProfileCapture per window")
        self._first = steps[0] if steps else None
        self._last = steps[-1] if steps else None
        self.logdir = logdir
        self.annotation = annotation
        self._active = False
        self._step_depth = 0    # open step() scopes (re-entry guard)
        self._fired = False     # did a trace window ever open?

    @property
    def active(self) -> bool:
        return self._active

    @contextlib.contextmanager
    def step(self, i: int):
        """Wrap one training step; starts/stops the trace at the window
        edges and annotates the step body."""
        if self._step_depth > 0 and self._active:
            # re-entering while a trace window is OPEN (a nested `with
            # cap.step(...)`, or a generator/except path that never
            # unwound the previous scope) — a NAMED error, because the
            # silent alternative is a trace whose "steps" are hulls of
            # their children; outside a window the nesting is inert
            # (no annotation emitted) and stays permitted
            raise ProfileStepReentryError(
                f"ProfileCapture.step({i}) entered while a previous "
                "step scope's trace window is still open — one scope "
                "per training step, sequentially")
        # the depth (not a bool) keeps inert nesting from opening the
        # window nested or resetting the guard for its outer scope:
        # only a TOP-LEVEL step entry may arm the trace
        if (self._step_depth == 0
                and not self._active and not self._fired
                and self._first is not None
                and self._first <= i <= self._last):
            jax.profiler.start_trace(self.logdir)
            self._active = True
            self._fired = True
        if self._active:
            # StepTraceAnnotation groups the step in the trace viewer's
            # step axis
            ann = jax.profiler.StepTraceAnnotation(self.annotation,
                                                   step_num=i)
        else:
            ann = contextlib.nullcontext()
        self._step_depth += 1
        try:
            with ann:
                yield self
        finally:
            self._step_depth -= 1
            if self._active and i >= self._last \
                    and self._step_depth == 0:
                self.close()

    def close(self) -> None:
        """Stop the trace if armed (idempotent)."""
        if self._active:
            self._active = False
            jax.profiler.stop_trace()

    def trace_path(self) -> Optional[str]:
        """Path of the newest `trace.json.gz` the capture wrote under
        `logdir` — what `monitor.timeline.analyze_trace` consumes.
        None when no window ever fired (the loop never reached
        `first`) or the profiler produced no trace file.  Resolved at
        call time: the profiler writes the file on `stop_trace`, so
        call this after the window closed (`close()` or the last
        step's exit)."""
        if not self._fired:
            return None
        from apex_tpu.monitor.timeline import events as _ev
        return _ev.newest_trace(self.logdir)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def profile_capture(step_range: Iterable[int], *,
                    logdir: str = "/tmp/apex_tpu_trace",
                    annotation: str = "train-step") -> ProfileCapture:
    """Build a `ProfileCapture` for the given step window (see module
    docstring for the loop idiom)."""
    return ProfileCapture(step_range, logdir=logdir, annotation=annotation)
