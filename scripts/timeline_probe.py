"""Runtime-timeline CI gate for the flagship train steps (ISSUE 15).

usage:
  python scripts/timeline_probe.py [targets...]  # default: gpt gpt_zero2
  python scripts/timeline_probe.py --selftest    # fixture drift gate +
                                                 # seeded negative controls
  python scripts/timeline_probe.py --steps N     # capture window (default 3)
  python scripts/timeline_probe.py --json        # machine-readable reports
  python scripts/timeline_probe.py --backend tpu # device truth on hardware

Where `comms_probe.py` gates what the schedule is PREDICTED to do,
this probe gates the measured plane end to end: build each flagship
step (the EXACT bench programs; CPU smoke configs substitute, same
build path), warm it up, arm a `monitor.ProfileCapture` over N steady
steps, EXECUTE them, and run `monitor.timeline.analyze_trace` on the
trace the profiler wrote.  Structure asserts (nonzero exit on any):

  * the trace parsed and carries device events (`n_device_events > 0`
    — a capture that saw only python is a broken profiler wiring),
  * the step count matches the capture window (N annotated steps in,
    N step anatomies out),
  * per-category wall-time fractions sum to ~1 (the attribution
    dropped or double-counted nothing),
  * the report round-trips its JSON schema (`validate_timeline_
    report`), the `timeline_probe --selftest` drift contract.

On the ZeRO-2 dp target the probe also closes the predicted-vs-
measured loop: `crosscheck_comms(timeline, comms_report)` must
produce a row for every counted collective — every expected-overlap
collective included — and on a measurable backend (TPU) a DIVERGES
row or a measured-serialized collective fails the gate.  On CPU the
backend emits sync collectives through an emulated-device thunk pool,
so the overlap plane is honestly UNMEASURABLE (asserted, printed,
PASS) — exactly the comms_probe convention; the parser/anatomy layer
is still fully exercised.

`--selftest` validates + renders the committed fixture
(scripts/timeline_fixture.json), checks its seeded MEASURED-SERIALIZED
collective is still flagged, and runs two seeded in-code controls: an
idle-heavy trace that must trip the DEVICE IDLE verdict BY NAME (the
negative control) and a busy trace that must not.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# scripts/ itself, for the shared gpt_anatomy/comms_probe builders
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

# resolve the backend BEFORE the first jax import (argv peek, the
# comms_probe convention): the probe EXECUTES steps, so `--backend
# tpu` is the operator's explicit ask for device truth
if "--backend" in sys.argv[1:]:
    try:
        os.environ["JAX_PLATFORMS"] = \
            sys.argv[sys.argv.index("--backend") + 1]
    except IndexError:
        sys.exit("--backend needs a value (e.g. --backend tpu)")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the ZeRO-2 target needs a dp axis: on the CPU backend force a 2-way
# virtual mesh (must precede the first jax import, conftest-style)
if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()

_HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(_HERE, "timeline_fixture.json")

# markers the fixture rendering must contain; losing one means the
# renderer no longer tells the story the fixture encodes
_FIXTURE_MARKERS = (
    "=== timeline: fixture-step ===",
    "| step |",
    "aggregate: device busy",
    "collective",
    "collective-permute",
    "**SER**",
    "MEASURED-SERIALIZED",
)

# the seeded serialized-chunk negative control (ISSUE 18): one chunk
# of the fixture's chunked-TP ring pair is seeded MEASURED-SERIALIZED
# and must stay flagged BY NAME — a renderer or analyzer change that
# stops surfacing a serialized ring hop would blind the measured gate
# to exactly the regression chunked overlap exists to prevent
_SEEDED_SERIALIZED_CHUNK = "collective-permute.8"


# ------------------------- seeded control traces -------------------------

def _seeded_trace(busy_frac: float, n_steps: int = 3) -> dict:
    """A deterministic TPU-style trace: per step a fixed wall window
    with device ops covering `busy_frac` of it — the in-code seed for
    the selftest's idle/busy controls (no profiler, no backend)."""
    wall = 10_000.0  # us per step
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 10, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
    ]
    for i in range(n_steps):
        t0 = i * wall
        events.append({"ph": "X", "pid": 9, "tid": 1,
                       "name": "train-step", "ts": t0, "dur": wall,
                       "args": {"step_num": str(i)}})
        events.append({"ph": "X", "pid": 1, "tid": 10, "name": "fusion.1",
                       "ts": t0 + 10.0, "dur": busy_frac * wall,
                       "args": {"hlo_op": "fusion.1"}})
    return {"traceEvents": events}


def selftest() -> int:
    from apex_tpu.monitor import timeline

    with open(FIXTURE) as f:
        rep = json.load(f)
    try:
        timeline.validate_timeline_report(rep)
        text = timeline.render_timeline_table(rep, label="fixture-step")
    except ValueError as e:
        print(f"timeline_probe --selftest: SCHEMA DRIFT — {e}",
              file=sys.stderr)
        print("(bump-side change? update scripts/timeline_fixture.json "
              "to the new schema)", file=sys.stderr)
        return 1
    missing = [m for m in _FIXTURE_MARKERS if m not in text]
    if missing:
        print(text)
        print(f"timeline_probe --selftest: rendering lost expected "
              f"markers: {missing}", file=sys.stderr)
        return 1
    ser = [c for c in rep["collectives"] if c.get("serialized")]
    if not ser or rep.get("measured_overlap_ok") is not False:
        print("timeline_probe --selftest: the fixture's seeded "
              "measured-serialized collective is no longer flagged — "
              "the gate is blind", file=sys.stderr)
        return 1
    if _SEEDED_SERIALIZED_CHUNK not in {c["name"] for c in ser}:
        print("timeline_probe --selftest: the seeded serialized ring "
              f"CHUNK ({_SEEDED_SERIALIZED_CHUNK}) is no longer "
              "flagged — the measured gate is blind to chunked-"
              "overlap regressions", file=sys.stderr)
        return 1
    if _SEEDED_SERIALIZED_CHUNK not in text:
        print("timeline_probe --selftest: the serialized ring chunk "
              "vanished from the rendering", file=sys.stderr)
        return 1
    print(text)

    # negative control, BY NAME: a seeded idle-heavy trace (device
    # busy 10% of each step) must trip the DEVICE IDLE verdict
    idle = timeline.analyze_trace(_seeded_trace(busy_frac=0.1))
    idle_text = timeline.render_timeline_table(idle, label="idle-seed")
    if (idle.device_busy_fraction >= timeline.IDLE_BUSY_FLOOR
            or "DEVICE IDLE" not in idle_text):
        print(idle_text)
        print("timeline_probe --selftest: the seeded idle-heavy trace "
              "did NOT trip the DEVICE IDLE verdict — the negative "
              "control is dead", file=sys.stderr)
        return 1
    print(f"negative control: idle-heavy seed (busy "
          f"{idle.device_busy_fraction:.2f}) flagged DEVICE IDLE — OK")
    # ...and a busy trace must NOT trip it (the verdict discriminates)
    busy = timeline.analyze_trace(_seeded_trace(busy_frac=0.9))
    if "DEVICE IDLE" in timeline.render_timeline_table(busy):
        print("timeline_probe --selftest: the busy seed tripped "
              "DEVICE IDLE — the verdict lost its floor",
              file=sys.stderr)
        return 1
    print("timeline_probe --selftest: OK")
    return 0


# ------------------------------ full probe ------------------------------

def _materialize(args):
    """Real zero-filled arrays for the builders' ShapeDtypeStructs —
    the probe EXECUTES the step (token id 0 is valid in every
    config)."""
    import jax
    import jax.numpy as jnp

    def mat(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jnp.zeros(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(
        mat, args, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _build(target, on_tpu):
    """(step, abstract_args, runner) for one probe target.  The
    abstract args feed `comms_report` (AOT, the predicted side); the
    runner executes one step on materialized state, rebinding donated
    buffers."""
    if target == "gpt_zero2":
        import comms_probe

        step, (state, scaler, batch) = comms_probe._build_gpt_zero2(
            on_tpu)
        live = [_materialize(state), scaler, _materialize(batch)]

        def run():
            out = step(live[0], live[1], live[2])
            live[0], live[1] = out[0], out[1]
            return out[2]

        return step, (state, scaler, batch), run
    if target == "gpt_tp_overlap":
        # the chunked-TP flagship (ISSUE 18): the ppermute-ring /
        # chunked-reduce program whose measured per-hop overlap the
        # crosscheck below judges against the AOT prediction
        import comms_probe

        step, (opt_state, tokens, labels) = \
            comms_probe._build_gpt_tp_overlap(on_tpu)
        live = [_materialize(opt_state), _materialize(tokens),
                _materialize(labels)]

        def run():
            out = step(live[0], live[1], live[2])
            live[0] = out[0]
            return out[1]

        return step, (opt_state, tokens, labels), run
    import gpt_anatomy

    key = {"gpt": "350m", "bert": "bert"}[target]
    _, step, (opt_state, tokens, labels), _ = \
        gpt_anatomy._build_bench_step(key, on_tpu, mode="comms")
    live = [opt_state, _materialize(tokens), _materialize(labels)]

    def run():
        out = step(live[0], live[1], live[2])
        live[0] = out[0]
        return out[1]

    return step, (opt_state, tokens, labels), run


TARGETS = ("gpt", "gpt_zero2", "bert", "gpt_tp_overlap")
DEFAULT_TARGETS = ("gpt", "gpt_zero2", "gpt_tp_overlap")


def _probe_target(target, n_steps, logdir, as_json) -> int:
    import jax

    from apex_tpu import monitor
    from apex_tpu.monitor import comms as comms_lib
    from apex_tpu.monitor import timeline

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    step, abstract_args, run = _build(target, on_tpu)

    # two warmups absorb the compile (+ the donated-layout second
    # compile, the bench.py rule) so the capture holds STEADY steps
    for _ in range(2):
        jax.block_until_ready(run())
    cap = monitor.profile_capture(
        range(0, n_steps), logdir=os.path.join(logdir, target))
    try:
        for i in range(n_steps):
            with cap.step(i):
                jax.block_until_ready(run())
    finally:
        cap.close()  # a raise mid-capture must stop the profiler
        # (a leaked open trace would poison the NEXT target's capture)

    path = cap.trace_path()
    if path is None:
        print(f"timeline_probe {target}: FAIL — the capture window "
              "fired but no trace.json.gz was written", file=sys.stderr)
        return 1
    rep = timeline.analyze_trace(path)

    rc = 0
    # structure asserts — the gate proper
    if rep.n_device_events <= 0:
        print(f"timeline_probe {target}: FAIL — trace parsed to ZERO "
              "device events", file=sys.stderr)
        rc = 1
    if len(rep.steps) != n_steps:
        print(f"timeline_probe {target}: FAIL — captured {n_steps} "
              f"steps but the anatomy holds {len(rep.steps)}",
              file=sys.stderr)
        rc = 1
    frac_sum = sum(rep.category_fractions.values())
    if rep.n_device_events > 0 and abs(frac_sum - 1.0) > 1e-6:
        print(f"timeline_probe {target}: FAIL — category fractions "
              f"sum to {frac_sum}, not ~1", file=sys.stderr)
        rc = 1
    try:
        timeline.validate_timeline_report(
            json.loads(json.dumps(rep.to_dict())))
    except ValueError as e:
        print(f"timeline_probe {target}: FAIL — schema round-trip: "
              f"{e}", file=sys.stderr)
        rc = 1
    # backend honesty: a CPU capture must never fake the overlap plane
    if not on_tpu and (rep.overlap_measurable
                       or rep.measured_overlap_ok is not None):
        print(f"timeline_probe {target}: FAIL — CPU capture claims a "
              "measurable overlap plane", file=sys.stderr)
        rc = 1
    if rep.overlap_measurable and rep.measured_overlap_ok is False:
        print(f"timeline_probe {target}: FAIL — measured-serialized "
              "collective(s) in the schedule", file=sys.stderr)
        rc = 1

    xc = None
    if target in ("gpt_zero2", "gpt_tp_overlap"):
        # the predicted-vs-measured loop: one row per counted
        # collective of the AOT report, expected-overlap ones
        # included.  On the chunked-TP target this is where the
        # chunk-count-many ring hops meet their measured spans — the
        # name-prefix grouping in crosscheck_comms keeps a chunk's
        # span with its own logical collective when the trace
        # renumbers instances
        crep = comms_lib.comms_report(step, abstract_args)
        xc = timeline.crosscheck_comms(rep, crep)
        n_counted = sum(crep.to_dict()["counts"].values())
        if len(xc["rows"]) != n_counted:
            print(f"timeline_probe {target}: FAIL — crosscheck has "
                  f"{len(xc['rows'])} rows for {n_counted} counted "
                  "collective(s)", file=sys.stderr)
            rc = 1
        missing = [r["name"] for r in xc["rows"]
                   if r["expected_overlap"]
                   and r["measured_overlap_fraction"] is None
                   and rep.overlap_measurable]
        if missing:
            print(f"timeline_probe {target}: FAIL — expected-overlap "
                  f"collective(s) unmatched in the trace: {missing}",
                  file=sys.stderr)
            rc = 1
        if rep.overlap_measurable and not xc["ok"]:
            print(f"timeline_probe {target}: FAIL — predicted vs "
                  f"measured overlap DIVERGES on "
                  f"{xc['n_diverge']} collective(s)", file=sys.stderr)
            rc = 1

    if as_json:
        print(json.dumps({"target": target, "report": rep.to_dict(),
                          "crosscheck": xc, "ok": rc == 0}))
    else:
        print(timeline.render_timeline_table(rep, label=target))
        if xc is not None:
            print(timeline.render_crosscheck(xc, label=target))
        if not rep.overlap_measurable:
            print("overlap plane: UNMEASURABLE on this backend "
                  "(honest) — gate judges structure only")
        print(f"timeline_probe {target}: "
              f"{'PASS' if rc == 0 else 'FAIL'}")
        print()
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(
        description="runtime-timeline CI gate for the flagship steps")
    ap.add_argument("targets", nargs="*",
                    help=f"subset of {sorted(TARGETS)} "
                         f"(default: {list(DEFAULT_TARGETS)})")
    ap.add_argument("--selftest", action="store_true",
                    help="fixture drift gate + seeded idle/busy "
                         "controls; exit 1 on drift")
    ap.add_argument("--steps", type=int, default=3,
                    help="steady steps to capture (default 3)")
    ap.add_argument("--logdir", default=None,
                    help="keep traces here (default: a temp dir)")
    ap.add_argument("--backend", metavar="NAME", default=None,
                    help="JAX_PLATFORMS for the run (e.g. tpu); "
                         "consumed before the first jax import by the "
                         "argv peek above — registered here so "
                         "argparse accepts it")
    ap.add_argument("--json", action="store_true",
                    help="print JSON instead of tables")
    args = ap.parse_args()
    if args.selftest:
        return selftest()

    targets = args.targets or list(DEFAULT_TARGETS)
    bad = [t for t in targets if t not in TARGETS]
    if bad:
        ap.error(f"unknown target(s) {bad}; choices: {sorted(TARGETS)}")

    logdir = args.logdir or tempfile.mkdtemp(prefix="timeline_probe_")

    from apex_tpu.parallel import mesh as M

    rc = 0
    for t in targets:
        rc |= _probe_target(t, args.steps, logdir, args.json)
        M.destroy_model_parallel()
    if not args.json:
        verdict = "PASS" if rc == 0 else "FAIL"
        print(f"timeline_probe: {len(targets)} target(s), {verdict}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
