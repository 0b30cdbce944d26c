"""apex_tpu.monitor — on-device training telemetry (ISSUE 2).

Three layers:

  * metrics  — `MetricsState`, a tiny all-scalar pytree that rides
               INSIDE jitted train steps (no host syncs to collect);
               the hot paths (`parallel.ddp.make_train_step`,
               `schedules.forward_backward_no_pipelining`,
               `amp.FP16_Optimizer.step`) thread it via their optional
               `metrics=` hooks
  * logger   — host-side `MetricsLogger` + sinks (JSONL / console /
               SummaryWriter adapter) + derived rates (step time,
               tokens/sec, MFU from `monitor.flops` accounting)
  * profiler — `profile_capture(step_range)`: jax.profiler trace armed
               over a chosen step window
  * trace    — the numerics flight recorder (ISSUE 4): per-layer stat
               taps with NaN/overflow provenance, cross-rank timing +
               straggler detection, and the crash-dump ring buffer
               (`monitor.trace` subpackage)
  * compile  — the compile & HBM observatory (ISSUE 5): AOT memory/
               cost audit (`analyze_step` -> `CompileReport`, HBM
               budget table, donation + flops cross-checks), the
               `RecompileSentry`, and device-memory watermarks + OOM
               forensics (`monitor.compile` subpackage)
  * comms    — the collective & overlap observatory (ISSUE 7):
               optimized-HLO collective inventory
               (`comms_report` -> `CommsReport`), async start/done
               overlap classification, and the per-device-kind ICI
               roofline (`monitor.comms` subpackage; CI-gated by
               `scripts/comms_probe.py`)
  * timeline — the runtime timeline observatory (ISSUE 15): parses
               the profiler traces `ProfileCapture` writes into a
               MEASURED per-step anatomy (`analyze_trace` ->
               `TimelineReport`: device-busy/host-gap, category
               attribution, per-collective measured overlap) and
               cross-checks the comms plane's predictions
               (`crosscheck_comms`; CI-gated by
               `scripts/timeline_probe.py`)

  * scopes   — the step named from inside (ISSUE 25): the
               vocabulary of `jax.named_scope` paths and Pallas kernel
               names the program uses, the rule that says which scope
               owns each instruction of a compiled step
               (`scopes.owners`), and the step a process ran
               (`scopes.step_owners`); a trace's device time by owner
               is a lookup in that map

See docs/observability.md for the JSONL schema and recipes, and
examples/train_with_monitor.py for the end-to-end loop.
"""

from apex_tpu.monitor import flops  # noqa: F401
from apex_tpu.monitor.flops import (  # noqa: F401
    DEVICE_BF16_PEAKS,
    V5E_BF16_PEAK,
    bert_step_flops,
    device_peak_flops,
    gpt_step_flops,
    mfu,
    transformer_step_flops,
)
from apex_tpu.monitor import compile  # noqa: F401,A004 — subpackage
from apex_tpu.monitor.compile import (  # noqa: F401
    CompileReport,
    RecompileSentry,
    analyze_step,
    device_memory_stats,
    render_budget_table,
)
from apex_tpu.monitor import comms  # noqa: F401
from apex_tpu.monitor.comms import (  # noqa: F401
    DEVICE_ICI_BANDWIDTH,
    CommsReport,
    comms_report,
    device_link_bandwidth,
    render_comms_table,
)
from apex_tpu.monitor import scopes  # noqa: F401
from apex_tpu.monitor import timeline  # noqa: F401
from apex_tpu.monitor.timeline import (  # noqa: F401
    TIMELINE_SCHEMA_VERSION,
    TimelineReport,
    TraceParseError,
    analyze_trace,
    crosscheck_comms,
    render_timeline_table,
    validate_timeline_report,
)
from apex_tpu.monitor.logger import (  # noqa: F401
    SCHEMA,
    SCHEMA_VERSION,
    MetricsLogger,
    validate_record,
    validate_records,
)
from apex_tpu.monitor.metrics import (  # noqa: F401
    MetricsConfig,
    MetricsState,
    global_norm,
    infer_tokens_per_step,
    init_metrics,
    update_metrics,
)
from apex_tpu.monitor.profiler import (  # noqa: F401
    ProfileCapture,
    ProfileStepReentryError,
    profile_capture,
)
from apex_tpu.monitor.sinks import (  # noqa: F401
    ConsoleSink,
    JSONLSink,
    MetricSink,
    ScalarWriter,
    SummaryWriterSink,
    sanitize_json_floats,
)
from apex_tpu.monitor import trace  # noqa: F401
from apex_tpu.monitor.trace import (  # noqa: F401
    FlightRecorder,
    StragglerDetector,
    TapState,
    TraceConfig,
)
