"""Serving-SLO CI gate (ISSUE 10): drive the flagship engine under a
churn workload and hold the serving observatory to its contract.

usage:
  python scripts/slo_probe.py             # full probe
  python scripts/slo_probe.py --selftest  # fixture drift gate
  python scripts/slo_probe.py --json      # machine-readable result

The full probe builds the flagship serve engine
(`serve.build_flagship_engine` — the SAME program bench.py measures
and the lint/comms gates probe) and drives a churn workload (more
requests than slots, ragged prompts and budgets) through
`measure_decode`, then asserts:

  1. LEDGER      — the request-lifecycle ledger reconciles EXACTLY
                   with the engine's own accounting: submitted ==
                   admitted == retired == the summed `(admitted,
                   retired)` that `step()` returned, per-request
                   token counts match the FinishedRequests, and every
                   record is causally ordered (submit <= admit <=
                   first-token <= retire).
  2. QUEUE       — with requests > slots, head-of-line-blocked
                   requests show nonzero queue wait (the gauge plane
                   has teeth, not zeros).
  3. ESTIMATOR   — the streaming percentile estimators agree with the
                   NumPy oracle over the same samples (exact below
                   reservoir capacity — this workload is below it).
  4. SLO         — the `ServeSLO` verdict is green under the given
                   thresholds (defaults are generous enough for any
                   CI box; tighten with the flags on real hardware)
                   and NO configured axis was skipped for lack of
                   samples.
  5. SENTRY      — zero steady-state recompiles under churn.
  6. BITWISE     — a telemetry-OFF engine over the same workload
                   produces byte-identical tokens (the observatory
                   observes, it never steers).

Exit is nonzero on any failure.  On a CPU backend the smoke config
substitutes through the same build path; on TPU run it as-is.

`--selftest` is the tier-1 fixture-drift gate (mirrors
`resume_probe.py --selftest`): the committed telemetry report
fixture (scripts/slo_fixture.json) must still validate against
`serve.validate_serve_report`, the estimator must reproduce the
NumPy oracle on a deterministic sample stream, and the fixture's
SEEDED SLO BREACH — a summary whose TTFT p99 violates its SLO — must
be reported as a breach naming the `ttft` axis (the gate's own
negative control: a verdict that stops flagging its seeded breach is
not a gate).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--backend" in sys.argv[1:]:
    try:
        os.environ["JAX_PLATFORMS"] = \
            sys.argv[sys.argv.index("--backend") + 1]
    except IndexError:
        sys.exit("--backend needs a value (e.g. --backend tpu)")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "slo_fixture.json")


# ---------------------------------------------------------------------------
# selftest (tier-1)
# ---------------------------------------------------------------------------

def selftest() -> int:
    import numpy as np

    from apex_tpu.serve import (ServeSLO, StreamingPercentiles,
                                validate_serve_report)

    with open(FIXTURE) as f:
        fixture = json.load(f)

    # 1. schema drift: the committed telemetry report must still
    # validate (bump-side change? regenerate scripts/slo_fixture.json
    # via `slo_probe.py --write-fixture`)
    try:
        validate_serve_report(fixture["report"])
    except ValueError as e:
        print(f"slo_probe --selftest: SCHEMA DRIFT — {e}",
              file=sys.stderr)
        print("(regenerate scripts/slo_fixture.json with "
              "`python scripts/slo_probe.py --write-fixture`)",
              file=sys.stderr)
        return 1

    # 2. estimator vs oracle on a deterministic stream: exact below
    # capacity, tolerance-bounded above it
    rng = np.random.RandomState(1234)
    small = rng.lognormal(mean=0.0, sigma=1.0, size=200)
    est = StreamingPercentiles(capacity=4096, seed=0)
    est.extend(small)
    for q in (50.0, 95.0, 99.0):
        got, want = est.percentile(q), float(np.percentile(small, q))
        if abs(got - want) > 1e-12 * max(1.0, abs(want)):
            print(f"slo_probe --selftest: estimator p{q:g} {got!r} != "
                  f"oracle {want!r} below capacity (must be EXACT)",
                  file=sys.stderr)
            return 1
    big = rng.lognormal(mean=0.0, sigma=1.0, size=20_000)
    est2 = StreamingPercentiles(capacity=2048, seed=0)
    est2.extend(big)
    p50, p99 = est2.percentile(50.0), est2.percentile(99.0)
    o50, o99 = (float(np.percentile(big, 50)),
                float(np.percentile(big, 99)))
    if abs(p50 - o50) / o50 > 0.15 or abs(p99 - o99) / o99 > 0.35:
        print(f"slo_probe --selftest: reservoir estimate drifted from "
              f"the oracle (p50 {p50:.4f} vs {o50:.4f}, p99 {p99:.4f} "
              f"vs {o99:.4f})", file=sys.stderr)
        return 1

    # 3. negative control: the committed SEEDED BREACH must fail, and
    # must fail on the axis it seeds — BY NAME.  A green verdict here
    # means ServeSLO lost its teeth.
    br = fixture["seeded_breach"]
    verdict = ServeSLO(**br["slo"]).evaluate_summary(br["summary"])
    if verdict.ok:
        print("slo_probe --selftest: seeded SLO breach was NOT "
              "flagged — ServeSLO.evaluate lost its teeth",
              file=sys.stderr)
        return 1
    axes = [b.axis for b in verdict.breaches]
    if br["expect_axis"] not in axes:
        print(f"slo_probe --selftest: seeded breach flagged axes "
              f"{axes}, expected {br['expect_axis']!r} named",
              file=sys.stderr)
        return 1
    pcts = [b.percentile for b in verdict.breaches
            if b.axis == br["expect_axis"]]
    if br["expect_percentile"] not in pcts:
        print(f"slo_probe --selftest: seeded breach on "
              f"{br['expect_axis']!r} reported percentile {pcts}, "
              f"expected {br['expect_percentile']!r}", file=sys.stderr)
        return 1
    # the breach text must NAME the axis (what an operator greps for)
    if br["expect_axis"] not in verdict.describe():
        print("slo_probe --selftest: verdict text does not name the "
              f"violated axis: {verdict.describe()!r}", file=sys.stderr)
        return 1
    print("slo_probe --selftest: OK")
    return 0


# ---------------------------------------------------------------------------
# full probe
# ---------------------------------------------------------------------------

def _churn_workload(eng, n_requests, max_new_cap, seed=0):
    """Submit a ragged churn workload: more requests than slots,
    ragged prompt lengths and budgets (deterministic)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    mp = eng.serve_cfg.max_prompt_len
    rids = []
    for _ in range(n_requests):
        plen = int(rng.randint(1, mp + 1))
        budget = int(rng.randint(1, max_new_cap + 1))
        prompt = rng.randint(0, eng.model_cfg.vocab_size, plen).tolist()
        rids.append(eng.submit(prompt, budget))
    return rids


def probe(args) -> int:
    import numpy as np

    from apex_tpu.serve import (ServeSLO, build_flagship_engine,
                                measure_decode, validate_serve_report)

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    slo = ServeSLO(ttft_p99_ms=args.slo_ttft_p99_ms,
                   per_token_p99_ms=args.slo_token_p99_ms,
                   max_queue_wait_ms=args.slo_queue_wait_ms)
    eng = build_flagship_engine(on_tpu)
    eng.slo = slo
    n_slots = eng.serve_cfg.n_slots
    n_requests = args.requests or 3 * n_slots
    # the probe's per-request tail checks and estimator-vs-oracle
    # EXACTNESS need the full run retained: size the telemetry to the
    # workload (the default 1024-tail / 4096-reservoir caps would
    # turn a healthy --requests 5000 run into bogus FAILs)
    from apex_tpu.serve import ServeTelemetry
    eng.telemetry = ServeTelemetry(
        tail_cap=n_requests + 8,
        estimator_capacity=max(4096, n_requests + 8))
    max_new = min(args.max_new or (16 if on_tpu else 8),
                  eng.serve_cfg.max_new_cap)
    rids = _churn_workload(eng, n_requests, max_new)

    failures = []
    result = {"backend": "tpu" if on_tpu else "cpu",
              "n_slots": n_slots, "n_requests": n_requests,
              "max_new": max_new}
    m = measure_decode(eng, max_steps=n_requests * max_new + 64)
    led = eng.telemetry.ledger
    result["steps"] = m["steps"]
    result["churn_steps"] = m["churn_steps"]
    result["tokens_per_sec"] = round(m["tokens_per_sec"], 1)

    # 1. ledger <-> engine reconciliation (exact).  A healthy
    # deadline-less run must also show ZERO terminal casualties and a
    # closed balance identity (ISSUE 14): expiry/shed/cancel firing
    # here would mean the resilience plane steers healthy traffic.
    bal = led.balance()
    ok = (led.n_submitted == led.n_admitted == led.n_retired
          == m["admitted"] == m["retired"] == n_requests
          and led.n_open == 0)
    result["ledger_reconciles"] = ok
    if not ok:
        failures.append(
            f"ledger does not reconcile: submitted {led.n_submitted} / "
            f"admitted {led.n_admitted} / retired {led.n_retired} vs "
            f"step() sums admitted {m['admitted']} / retired "
            f"{m['retired']} over {n_requests} requests "
            f"({led.n_open} still open)")
    if not bal["ok"]:
        failures.append(f"terminal-state balance violated: {bal}")
    if led.n_shed or led.n_expired or led.n_cancelled:
        failures.append(
            f"healthy run hit terminal states: shed {led.n_shed} / "
            f"expired {led.n_expired} / cancelled {led.n_cancelled} — "
            "the resilience plane fired on deadline-less traffic")
    fin_tokens = {f.request_id: len(f.tokens) for f in m["finished"]}
    tail = {r.request_id: r for r in led.tail}
    if set(fin_tokens) != set(rids):
        failures.append("finished request ids != submitted ids")
    for rid, n in fin_tokens.items():
        rec = tail.get(rid)
        if rec is None:
            failures.append(f"request {rid} missing from ledger tail")
            continue
        if rec.n_tokens != n:
            failures.append(
                f"request {rid}: ledger n_tokens {rec.n_tokens} != "
                f"{n} tokens actually returned")
        stamps = (rec.submit_t, rec.admit_t, rec.first_token_t,
                  rec.retire_t)
        if any(s is None for s in stamps) or not all(
                a <= b for a, b in zip(stamps, stamps[1:])):
            failures.append(
                f"request {rid}: lifecycle stamps out of order "
                f"{stamps}")
    if led.tokens_emitted != sum(fin_tokens.values()):
        failures.append(
            f"ledger tokens_emitted {led.tokens_emitted} != "
            f"{sum(fin_tokens.values())} returned")

    # 2. queueing has teeth: requests > slots must show head-of-line
    # waits strictly above the first-admitted cohort's
    waits = [r.queue_wait_s for r in led.tail]
    result["queue_wait_max_ms"] = round(1e3 * max(waits), 3)
    if n_requests > n_slots and max(waits) <= 0:
        failures.append(
            "requests > slots but no request shows queue wait — the "
            "queue-wait plane is not measuring")

    # 3. estimator vs oracle over the SAME samples (exact: this
    # workload is below reservoir capacity)
    for name, est, samples in (
            ("ttft", led.ttft, [r.ttft_s for r in led.tail]),
            ("queue_wait", led.queue_wait, waits),
            ("per_token", led.token_lat,
             [r.per_token_s for r in led.tail
              if r.per_token_s is not None])):
        if not samples:
            continue
        got = est.percentile(99.0)
        want = float(np.percentile(samples, 99))
        result[f"{name}_p99_ms"] = round(1e3 * got, 3)
        if abs(got - want) > 1e-9 * max(1.0, abs(want)):
            failures.append(
                f"{name} estimator p99 {got!r} != numpy oracle "
                f"{want!r} on the same {len(samples)} samples")

    # 4. the SLO verdict (no configured axis may be skipped: an axis
    # with no samples cannot claim green)
    verdict = eng.slo_verdict()
    result["slo_ok"] = verdict.ok
    result["slo"] = slo.to_dict()
    if not verdict.ok:
        failures.append(verdict.describe())
    if verdict.skipped:
        failures.append(
            f"SLO axes with no samples: {verdict.skipped} — the probe "
            "must measure every configured axis")

    # 5. zero steady-state recompiles under churn
    result["recompile_ok"] = eng.recompile_ok
    if not eng.recompile_ok:
        failures.append(
            f"steady-state recompile under churn: "
            f"{eng.sentry.summary()}")

    # 6. the observatory observes, it never steers: telemetry-off
    # engine, same weights + workload, byte-identical tokens
    eng_off = build_flagship_engine(on_tpu, params=eng.params)
    eng_off.telemetry = None
    rids_off = _churn_workload(eng_off, n_requests, max_new)
    fins_off = {f.request_id: f.tokens
                for f in eng_off.run(max_steps=n_requests * max_new + 64)}
    fins_on = {f.request_id: f.tokens for f in m["finished"]}
    bitwise = (dict(zip(rids, [fins_on[r] for r in rids]))
               == dict(zip(rids_off, [fins_off[r] for r in rids_off])))
    result["bitwise_telemetry_off"] = bitwise
    if not bitwise:
        failures.append(
            "decode outputs differ telemetry-on vs telemetry-off")

    # the report the crash dump would carry must be valid JSON-able
    try:
        rep = eng.telemetry_report()
        validate_serve_report(rep)
        json.dumps(rep)
    except (ValueError, TypeError) as e:
        failures.append(f"telemetry_report invalid: {e}")

    result["ok"] = not failures
    if args.json:
        # ONE line so callers can reverse-scan stdout past log noise
        print(json.dumps(result, sort_keys=True))
    else:
        for k in sorted(result):
            print(f"  {k}: {result[k]}")
    if failures:
        for f in failures:
            print(f"slo_probe: FAIL — {f}", file=sys.stderr)
        return 1
    print("slo_probe: OK (ledger reconciles, estimator == oracle, SLO "
          "green, zero steady-state recompiles, bitwise with "
          "telemetry off)")
    return 0


# ---------------------------------------------------------------------------
# fixture (re)generation — run once, commit the result
# ---------------------------------------------------------------------------

def write_fixture() -> int:
    from apex_tpu.serve import build_flagship_engine, measure_decode

    eng = build_flagship_engine(False)
    _churn_workload(eng, 2 * eng.serve_cfg.n_slots, 6)
    measure_decode(eng, max_steps=4096)
    fixture = {
        "_comment": "slo_probe --selftest fixture: a real smoke-run "
                    "telemetry report (schema drift gate) + a seeded "
                    "SLO breach (negative control).  Regenerate with "
                    "`python scripts/slo_probe.py --write-fixture`.",
        "report": eng.telemetry_report(),
        "seeded_breach": {
            "slo": {"ttft_p99_ms": 10.0, "per_token_p99_ms": 50.0,
                    "max_queue_wait_ms": 100.0},
            "summary": {"ttft_p99_ms": 25.0, "per_token_p99_ms": 1.0,
                        "queue_wait_max_ms": 2.0, "n_retired": 16},
            "expect_axis": "ttft",
            "expect_percentile": "p99",
        },
    }
    with open(FIXTURE, "w") as f:
        json.dump(fixture, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {FIXTURE}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description="serving observatory / SLO CI gate")
    ap.add_argument("--selftest", action="store_true",
                    help="fixture drift gate; exit 1 on drift")
    ap.add_argument("--write-fixture", action="store_true",
                    help="regenerate scripts/slo_fixture.json")
    ap.add_argument("--requests", type=int, default=None,
                    help="churn workload size (default 3x slots)")
    ap.add_argument("--max-new", type=int, default=None,
                    help="per-request token budget cap "
                         "(default 8 CPU / 16 TPU)")
    ap.add_argument("--slo-ttft-p99-ms", type=float, default=120_000.0,
                    help="TTFT p99 SLO in ms (default generous for "
                         "CI; tighten on real hardware)")
    ap.add_argument("--slo-token-p99-ms", type=float, default=60_000.0,
                    help="per-token p99 SLO in ms")
    ap.add_argument("--slo-queue-wait-ms", type=float,
                    default=240_000.0,
                    help="max queue wait SLO in ms")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable result")
    ap.add_argument("--backend", default=None,
                    help="JAX_PLATFORMS override (resolved pre-import)")
    args = ap.parse_args()
    if args.selftest:
        return selftest()
    if args.write_fixture:
        return write_fixture()
    return probe(args)


if __name__ == "__main__":
    sys.exit(main())
