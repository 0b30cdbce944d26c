"""The measured loop of a training job: steps dispatched back to back,
each followed by a wait for the loss of the step before it.

That is what a real loop that logs its loss does: the device's queue
never empties (step i is queued while step i - 1 runs), and each wait
returns when a step has completed, which gives a completion time a
step.  The rate is taken between completions, so it holds neither the
first dispatch nor the drain at the end.
"""

from __future__ import annotations

import time

import jax


class StepLog:
    """What the loop saw, step by step, from the very first step."""

    def __init__(self):
        self.losses = []          # one float a completed step
        self.dispatch_s = []      # host seconds inside step(...), a step
        self.completed_at = []    # perf_counter() when a loss arrived
        self.segments = []        # (first, last) step index of each run()


def run(step, state, batches, log: StepLog, *, seconds=None, steps=None):
    """Dispatch steps from the ring `batches` until `seconds` have
    passed or `steps` are out; returns the state.  The ring position
    follows the count of all steps so far, so a second call goes on
    where the first stopped.  Every loss is waited for before this
    returns."""
    first = len(log.losses)
    start = time.perf_counter()
    pending = None
    i = first
    while True:
        tokens, labels = batches[i % len(batches)]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, loss = step(state, tokens, labels)
        log.dispatch_s.append(time.perf_counter() - t0)
        if pending is not None:
            _collect(pending, log)
        pending = loss
        i += 1
        if steps is not None:
            if i - first >= steps:
                break
        elif time.perf_counter() - start >= seconds:
            break
    _collect(pending, log)
    log.segments.append((first, len(log.losses) - 1))
    return state


def _collect(loss, log: StepLog) -> None:
    with jax.profiler.TraceAnnotation("bench.wait_loss"):
        value = float(loss)   # returns when that step has completed
    log.completed_at.append(time.perf_counter())
    log.losses.append(value)


def rate_per_s(log: StepLog, segment: int, units_per_step: float) -> float:
    """Units a second over one run(): the steps after its first
    completion, over the time from that completion to the last."""
    first, last = log.segments[segment]
    elapsed = log.completed_at[last] - log.completed_at[first]
    return units_per_step * (last - first) / elapsed
