"""Render a numerics flight-recorder crash report (ISSUE 4).

usage:
  python scripts/flight_report.py REPORT.json [--last N]
  python scripts/flight_report.py --selftest

REPORT.json is what `monitor.trace.FlightRecorder.dump()` wrote (on an
exception inside `recorder.guard()`, or explicitly from a SIGTERM
handler).  The renderer prints the last-good → first-bad timeline with
the offending tap (layer + plane) highlighted, plus the cross-rank
straggler summary.

`--selftest` renders the committed fixture
(scripts/flight_report_fixture.json) and exits nonzero when the report
schema drifted or the rendering lost its load-bearing markers — the CI
guard that a report written by today's FlightRecorder stays readable by
today's renderer (mirrors `gpt_anatomy.py tune --check`).  Run from the
tier-1 suite (tests/test_trace.py).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# pure host-side rendering — a crash-report read on a dead machine
# must not wait for an accelerator
os.environ.setdefault("JAX_PLATFORMS", "cpu")

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "flight_report_fixture.json")

# markers the fixture rendering must contain; losing one means the
# renderer no longer tells the story the fixture encodes
_FIXTURE_MARKERS = (
    "first non-finite [grad] at block1/attn",
    "STRAGGLER rank 2",
    "last good step: 41001",
    "first bad step: 41002",
    # the compile & HBM observatory plane (ISSUE 5): the steady-state
    # retrace, the device watermark, and the HBM budget table
    "RECOMPILE at call 40970",
    "hbm[0]: 13.50 GiB in use / 14.00 GiB peak",
    "=== HBM budget ===",
    "donation: ok",
)


def selftest() -> int:
    from apex_tpu.monitor.trace import report as report_mod

    with open(FIXTURE) as f:
        rep = json.load(f)
    try:
        text = report_mod.render_report(rep)
    except ValueError as e:
        print(f"flight_report --selftest: SCHEMA DRIFT — {e}",
              file=sys.stderr)
        print("(bump-side change? update scripts/"
              "flight_report_fixture.json to the new schema)",
              file=sys.stderr)
        return 1
    missing = [m for m in _FIXTURE_MARKERS if m not in text]
    if missing:
        print(text)
        print(f"flight_report --selftest: rendering lost expected "
              f"markers: {missing}", file=sys.stderr)
        return 1
    print(text)
    print("flight_report --selftest: OK")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description="render a numerics flight-recorder report")
    ap.add_argument("report", nargs="?",
                    help="report JSON written by FlightRecorder.dump()")
    ap.add_argument("--last", type=int, default=None,
                    help="only the final N recorded steps")
    ap.add_argument("--selftest", action="store_true",
                    help="render the committed fixture; exit 1 on "
                         "schema drift")
    args = ap.parse_args()
    if args.selftest:
        return selftest()
    if not args.report:
        ap.error("REPORT.json required (or --selftest)")
    from apex_tpu.monitor.trace import report as report_mod

    with open(args.report) as f:
        rep = json.load(f)
    print(report_mod.render_report(rep, last=args.last))
    return 0


if __name__ == "__main__":
    sys.exit(main())
