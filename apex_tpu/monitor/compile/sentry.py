"""`RecompileSentry` — silent-recompile detection for jitted steps.

A steady-state retrace is the observability gap that turns into "the
run got 2x slower and nobody knows why": a batch whose leading dim
drifted, a dtype that flipped after a checkpoint reload, a python
scalar captured as a weak type.  XLA recompiles silently; the only
symptom is step time.

The sentry wraps the step callable.  When the underlying jitted
function exposes `_cache_size()` (the builders attach it as
`step.jitted`), the cache size is polled across each call — the
authoritative signal, catching compiles no argument change announces
(the donated-buffer layout second compile) — and the argument
signature (pytree structure + per-leaf shape/dtype; python scalars by
type+value — a changed scalar retraces too) is computed ONLY when a
compile actually fired, keeping per-step overhead out of timed
benchmark windows.  Without a reachable cache, every call is
fingerprinted and a new signature is the compile proxy.  Every
compile is recorded as
an event carrying the signature that triggered it; after
`mark_steady()` any further compile warns ONCE and counts in
`steady_recompiles` (bench.py asserts that stays 0 per config).

Pure host-side bookkeeping: the wrapped call is forwarded untouched,
so training numerics are bitwise identical with and without the
sentry (tests/test_compile_report.py).
"""

from __future__ import annotations

import warnings
from typing import Callable, Optional

from apex_tpu.monitor.compile import startup

_MAX_EVENTS = 64


def _sig_of(args, kwargs) -> str:
    """Stable shape/dtype signature of one call's arguments."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    parts = []
    for l in leaves:
        shape = getattr(l, "shape", None)
        dtype = getattr(l, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{tuple(shape)}:{dtype}")
        else:
            parts.append(f"{type(l).__name__}={l!r}")
    return f"{treedef}|{';'.join(parts)}"


class RecompileSentry:
    """Wrap a step: `sentry = RecompileSentry(step); sentry(*args)`.

    name: label in warnings/events.  recorder: an optional
    `trace.FlightRecorder` — every compile event is also pushed into
    its ring-side event list (`note_compile_event`) so a crash dump
    tells the recompile story too.  warn: emit the one-time
    steady-state warning (disable in benchmarks that assert instead).
    """

    def __init__(self, step_fn: Callable, *, name: str = "train_step",
                 recorder=None, warn: bool = True):
        self._fn = step_fn
        self.name = name
        self.recorder = recorder
        self.warn = warn
        self.calls = 0
        self.n_compiles = 0
        self.steady_recompiles = 0
        self.events = []          # [{call, kind, signature}]
        self._signatures = {}     # sig -> first-seen call index
        self._steady = False
        self._warned = False
        self._programs_seen = startup.n_programs()
        # poll the jit cache when reachable: the builders attach the
        # underlying jitted fn as `step.jitted`; a bare jitted step IS
        # its own cache owner
        cache_owner = getattr(step_fn, "jitted", step_fn)
        self._cache_size = getattr(cache_owner, "_cache_size", None)

    @property
    def wrapped(self):
        """The step underneath — for tools that need to TRACE the step
        without running the sentry's host-side bookkeeping on tracer
        arguments (apex_tpu.lint traces `wrapped`, else the trace
        would bump `calls` and pre-register the argument signature,
        hiding the genuine first compile from the signature-proxy
        path)."""
        return self._fn

    def _poll(self) -> Optional[int]:
        if self._cache_size is None:
            return None
        try:
            return int(self._cache_size())
        except Exception:  # never let introspection break a step
            return None

    def __call__(self, *args, **kwargs):
        before = self._poll()
        polled = before is not None
        # with a working cache poll the signature is only needed when a
        # compile actually happened — computing it per call would put a
        # pytree flatten + treedef repr inside timed benchmark windows
        # (a ~1000-leaf per-leaf state pays real string work per step)
        sig = None if polled else _sig_of(args, kwargs)
        out = self._fn(*args, **kwargs)
        after = self._poll()
        self.calls += 1
        if polled and after is not None:
            # cache growth is authoritative when visible
            compiled = after > before
        else:
            compiled = sig is not None and sig not in self._signatures
        if compiled:
            if sig is None:
                sig = _sig_of(args, kwargs)
            if sig not in self._signatures:
                self._signatures[sig] = self.calls
            self.n_compiles += 1
            event = {"call": self.calls,
                     "kind": ("compile" if self.n_compiles == 1
                              else "retrace"),
                     "steady_state": self._steady,
                     "signature": sig if len(sig) <= 512 else
                     sig[:509] + "..."}
            # the program this call just compiled, by the set-up
            # ledger's account (where it is armed): its name and seconds
            program = startup.heaviest_since(self._programs_seen)
            self._programs_seen = startup.n_programs()
            if program is not None:
                event["fun_name"] = program["fun_name"]
                event["seconds"] = startup.seconds_of(program)
            if len(self.events) < _MAX_EVENTS:
                self.events.append(event)
            if self.recorder is not None:
                try:
                    self.recorder.note_compile_event(
                        dict(event, name=self.name))
                except Exception:
                    pass
            if self._steady:
                self.steady_recompiles += 1
                if self.warn and not self._warned:
                    self._warned = True
                    warnings.warn(
                        f"RecompileSentry({self.name}): steady-state "
                        f"recompile at call {self.calls} — argument "
                        f"signature {event['signature']}; every such "
                        "step pays full XLA compilation",
                        RuntimeWarning, stacklevel=2)
        return out

    def mark_steady(self) -> None:
        """End of warmup: compiles were expected until now; from here
        every compile is a steady-state recompile (warned + counted),
        and the set-up ledger files it under `steady`."""
        self._steady = True
        self._programs_seen = startup.n_programs()
        startup.mark_steady()

    @property
    def n_signatures(self) -> int:
        return len(self._signatures)

    def summary(self) -> dict:
        """Flat JSON-able snapshot (bench.py stamps this per config)."""
        return {"calls": self.calls, "n_compiles": self.n_compiles,
                "n_signatures": self.n_signatures,
                "steady_recompiles": self.steady_recompiles}

    def __getattr__(self, item):
        # forward step attributes (tap_names, lower, donate_argnums,
        # arg_names ...) so a sentry-wrapped step still audits/labels
        return getattr(self._fn, item)
