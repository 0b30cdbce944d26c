"""apex_tpu.monitor.compile — the compile & HBM observatory (ISSUE 5).

The monitor stack's third axis, after "how fast" (metrics/MFU, ISSUE
2) and "where did numerics break" (trace, ISSUE 4): the compiled
program itself.  Four cooperating pieces:

  * report     — `analyze_step(step_fn, args) -> CompileReport`: AOT
                 lower+compile WITHOUT executing; per-program
                 argument/output/temp/alias bytes + generated-code
                 size (memory_analysis), flops/bytes-accessed
                 (cost_analysis), donation verification, the
                 analytic-flops cross-check that validates MFU, and
                 the HBM budget table (params / optimizer state /
                 activations+temps).
  * sentry     — `RecompileSentry`: wraps a jitted step, counts
                 traces/compiles, records the argument signature that
                 triggered each retrace, warns once on steady-state
                 recompiles; events ride into `MetricsLogger` records
                 and the `FlightRecorder` ring.
  * watermarks — per-log-interval `device.memory_stats()` sampling
                 (None on CPU, never a crash) and `is_oom` so the
                 flight-recorder guard can attach the last
                 CompileReport + memory snapshot to a
                 RESOURCE_EXHAUSTED crash dump.
  * startup    — the set-up ledger (ISSUE 36): every program the
                 process traces, lowers, compiles or reads from the
                 persistent cache, by name, stage and seconds (from
                 `jax.monitoring`), the seconds each Pallas kernel's
                 body took to trace (`kernel_span`, at the ops'
                 `pallas_call` sites), the program's own spans of
                 set-up work (`span`, also `apex.setup/<name>` in a
                 profile) and the bound on what came before it was
                 armed; `mark_steady` (the sentry's) files a later
                 compile under `steady`, by name and seconds.

See docs/observability.md ("HBM budget & recompile debugging", "Why
did start-up take N seconds").
"""

from apex_tpu.monitor.compile.report import (  # noqa: F401
    CompileReport,
    analyze_step,
    render_budget_table,
    tree_bytes,
)
from apex_tpu.monitor.compile.sentry import RecompileSentry  # noqa: F401
from apex_tpu.monitor.compile import startup  # noqa: F401
# NOTE: the module itself is deliberately NOT shadowed — the function
# export is named hbm_watermarks so `compile.watermarks` stays the
# submodule (recorder/logger import it by module path)
from apex_tpu.monitor.compile.watermarks import (  # noqa: F401
    WATERMARK_FIELDS,
    all_device_memory_stats,
    device_memory_stats,
    hbm_watermarks,
    is_oom,
)
