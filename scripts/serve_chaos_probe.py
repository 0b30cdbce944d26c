"""Serving-resilience chaos gate (ISSUE 14): drive the flagship
engine through an overload + kill matrix and hold the failure
semantics to their contract.

usage:
  python scripts/serve_chaos_probe.py             # full matrix
  python scripts/serve_chaos_probe.py --selftest  # fixture drift gate
  python scripts/serve_chaos_probe.py --json      # machine-readable

The full probe builds the flagship serve engine
(`serve.build_flagship_engine` — the SAME program bench.py measures
and the lint/comms/slo gates probe), records an UNLOADED baseline run
(every request alone against an unbounded queue, no faults), then
re-runs the same workload through every leg of the matrix and asserts,
for each:

  BITWISE     — every request that ends `ok` produces tokens bitwise
                equal to the unloaded baseline (overload, stalls,
                poisons and kills may shed/expire/cancel requests,
                but they may never CHANGE a survivor's output);
  POOL        — the page pool reconciles to zero leaks at every fail
                point (free pages == usable pages once drained);
  LEDGER      — the terminal-state balance identity closes exactly:
                n_submitted == n_retired + n_expired + n_cancelled +
                n_shed + n_open (`RequestLedger.balance`);
  SENTRY      — zero steady-state recompiles per engine.

Matrix legs (chaos points: `checkpoint.chaos.SERVE_POINTS`):

  overload    — bounded queue at 4x slot capacity with mixed
                deadlines + mid-run cancellation; negative controls
                asserted BY NAME: the seeded deadline breach ends
                `expired`, shed-under-overload fires (`shed` terminal,
                policy-ordered victim), the cancelled requests end
                `cancelled`;
  stall       — `serve.stall_step` wedges the decode loop; the
                `EngineWatchdog` must trip (`EngineStalledError`
                naming the stuck step — the watchdog-trip negative
                control), dump a flight report, and `restart()` from
                its periodic snapshot must resume MID-GENERATION
                bitwise;
  poison      — `serve.poison_logits` corrupts the output ring; the
                retire poll must refuse it (`PoisonedOutputError`
                naming slot/request/step) and the watchdog's
                last-KNOWN-GOOD snapshot must recover bitwise;
  kill-drain  — `serve.kill_mid_drain` kills a deploy's graceful
                drain partway; the snapshot contract recovers, the
                drained snapshot restores into a fresh engine, and
                the still-queued requests finish there bitwise.

Exit is nonzero on any failure.  On a CPU backend the smoke config
substitutes through the same build path; on TPU run it as-is.

`--selftest` is the tier-1 fixture-drift gate (mirrors
`slo_probe.py --selftest`): the committed telemetry report fixture
(scripts/serve_chaos_fixture.json) must still validate against
`serve.validate_serve_report`, and three SEEDED NEGATIVE CONTROLS
must fail by name without building an engine: a ledger whose deadline
breach must end `expired` with the balance identity closing, a
shed-policy replay whose named victim must be chosen, and a stub
engine whose watchdog must raise `EngineStalledError` naming the
stuck step under an injected clock.  A gate that stops flagging its
seeded failures is not a gate.
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
                       "serve_chaos_fixture.json")

# bound every drive loop: a wedged scheduler must FAIL the gate, not
# hang it (the serve_gpt example's convention)
_MAX_STEPS = 4096


# ---------------------------------------------------------------------------
# selftest (tier-1)
# ---------------------------------------------------------------------------

def selftest() -> int:
    from apex_tpu.serve import (EngineStalledError, EngineWatchdog,
                                RequestLedger, choose_shed_victim,
                                validate_serve_report)

    with open(FIXTURE) as f:
        fixture = json.load(f)

    # 1. schema drift: the committed chaos-run telemetry report must
    # still validate (bump-side change? regenerate with
    # `serve_chaos_probe.py --write-fixture`)
    try:
        validate_serve_report(fixture["report"])
    except ValueError as e:
        print(f"serve_chaos_probe --selftest: SCHEMA DRIFT — {e}",
              file=sys.stderr)
        print("(regenerate scripts/serve_chaos_fixture.json with "
              "`python scripts/serve_chaos_probe.py --write-fixture`)",
              file=sys.stderr)
        return 1
    led = fixture["report"]["ledger"]
    if not (led["n_shed"] > 0 and led["n_expired"] > 0
            and led["n_cancelled"] > 0 and led["balance_ok"]):
        print("serve_chaos_probe --selftest: the committed report no "
              "longer carries every terminal state with a closed "
              f"balance (shed {led['n_shed']} / expired "
              f"{led['n_expired']} / cancelled {led['n_cancelled']} / "
              f"balance_ok {led['balance_ok']})", file=sys.stderr)
        return 1

    # 2. negative control: DEADLINE BREACH.  A pure-ledger replay of
    # the seeded lifecycle — the expired request must end in terminal
    # `expired` BY NAME and the balance identity must still close.
    br = fixture["seeded_deadline_breach"]
    ledger = RequestLedger()
    ledger.on_submit(0, 4, 8, 0.0)
    ledger.on_submit(1, 4, 8, 0.0, deadline_ms=br["deadline_ms"])
    ledger.on_admit(0, 0, 0.001)
    ledger.on_first_token([0], 0.002)
    # the deadline passes while request 1 is still queued
    ledger.on_expire(1, br["deadline_ms"] / 1e3 + 0.001, where="queue")
    ledger.on_retire(0, 8, 0.01)
    rec = {r.request_id: r for r in ledger.tail}
    if rec[1].status != "expired" or rec[1].where != "queue":
        print(f"serve_chaos_probe --selftest: seeded deadline breach "
              f"ended {rec[1].status!r}/{rec[1].where!r}, expected "
              "'expired'/'queue' — the TTL terminal lost its name",
              file=sys.stderr)
        return 1
    bal = ledger.balance()
    if not bal["ok"] or bal["n_expired"] != 1:
        print(f"serve_chaos_probe --selftest: balance identity does "
              f"not close over the seeded breach: {bal}",
              file=sys.stderr)
        return 1
    # ...and a seeded IMBALANCE must be flagged: drop a terminal event
    bad = RequestLedger()
    bad.on_submit(0, 4, 8, 0.0)
    bad.on_submit(1, 4, 8, 0.0)
    bad.on_admit(0, 0, 0.001)
    bad.on_retire(0, 8, 0.01)
    bad._open.pop(1)              # the seeded hole: vanished request
    if bad.balance()["ok"]:
        print("serve_chaos_probe --selftest: seeded ledger imbalance "
              "(a request that vanished without a terminal state) was "
              "NOT flagged — balance() lost its teeth", file=sys.stderr)
        return 1

    # 3. negative control: SHED-UNDER-OVERLOAD policy ordering.  The
    # committed scenario replays through the ONE policy spelling the
    # engine uses; the named victim must be chosen.
    class _C:
        def __init__(self, rid, deadline_t):
            self.rid, self.deadline_t = rid, deadline_t

    sh = fixture["seeded_shed"]
    cands = [_C(c["rid"], c.get("deadline_t")) for c in sh["candidates"]]
    victim = choose_shed_victim(cands, sh["policy"])
    if victim.rid != sh["expect_victim"]:
        print(f"serve_chaos_probe --selftest: policy {sh['policy']!r} "
              f"shed rid {victim.rid}, fixture expects "
              f"{sh['expect_victim']} — shed ordering drifted",
              file=sys.stderr)
        return 1
    newest = choose_shed_victim(cands, "shed-newest")
    if newest.rid != cands[-1].rid:
        print("serve_chaos_probe --selftest: shed-newest did not pick "
              "the incoming request", file=sys.stderr)
        return 1

    # 4. negative control: WATCHDOG TRIP.  A stub engine that stops
    # heartbeating under an injected clock must raise
    # EngineStalledError naming the stuck step.
    class _StubEngine:
        steps_completed = 7
        pending = 3
        _live = {0: None, 1: None}
        _pending = [None]
        watchdog = None

    wd = fixture["seeded_watchdog"]
    t = [0.0]
    dog = EngineWatchdog(_StubEngine(),
                         stall_timeout_s=wd["stall_timeout_s"],
                         clock=lambda: t[0])
    dog.check()                        # armed, no progress yet
    t[0] = wd["stall_timeout_s"] + wd["overshoot_s"]
    try:
        dog.check()
    except EngineStalledError as e:
        if "step 7" not in str(e) or e.step != 7:
            print(f"serve_chaos_probe --selftest: watchdog trip does "
                  f"not name the stuck step: {e}", file=sys.stderr)
            return 1
    else:
        print("serve_chaos_probe --selftest: seeded stall did NOT "
              "trip the watchdog — EngineWatchdog lost its teeth",
              file=sys.stderr)
        return 1

    print("serve_chaos_probe --selftest: OK")
    return 0


# ---------------------------------------------------------------------------
# full probe
# ---------------------------------------------------------------------------

def _workload(eng, n_requests, max_new, seed=0, deadlines=None):
    """Deterministic ragged workload; `deadlines` (rid-index aligned)
    attaches per-request deadline_ms."""
    import numpy as np

    rng = np.random.RandomState(seed)
    mp = eng.serve_cfg.max_prompt_len
    rids = []
    for i in range(n_requests):
        plen = int(rng.randint(1, mp + 1))
        budget = int(rng.randint(1, max_new + 1))
        prompt = rng.randint(0, eng.model_cfg.vocab_size, plen).tolist()
        dl = deadlines[i] if deadlines else None
        rids.append(eng.submit(prompt, budget, deadline_ms=dl))
    return rids


def _drive(eng, fins, max_steps=_MAX_STEPS, watchdog=None):
    steps = 0
    while eng.pending:
        if steps >= max_steps:
            raise RuntimeError(f"drive: {eng.pending} request(s) still "
                               f"live after {max_steps} steps")
        eng.step()
        for f in eng.poll():
            fins[f.request_id] = f
        if watchdog is not None:
            watchdog.check()
        steps += 1
    return steps


def _leg_checks(name, eng, fins, ref, failures):
    """The invariants EVERY leg must hold: ok-survivors bitwise,
    pool reconciled, ledger balanced, sentry clean."""
    ok = {r: f.tokens for r, f in fins.items() if f.status == "ok"}
    for rid, toks in ok.items():
        if toks != ref[rid]:
            failures.append(
                f"{name}: request {rid} survived with NON-BITWISE "
                f"tokens vs the unloaded baseline")
            break
    if eng.cache.free_pages != eng.kv_config.usable_pages:
        failures.append(
            f"{name}: page pool leaked — {eng.cache.free_pages} free "
            f"of {eng.kv_config.usable_pages} usable after the storm")
    if eng.telemetry is not None:
        bal = eng.telemetry.ledger.balance()
        if not bal["ok"]:
            failures.append(f"{name}: ledger balance violated: {bal}")
    if not eng.recompile_ok:
        failures.append(f"{name}: steady-state recompile — "
                        f"{eng.sentry.summary()}")
    return ok


def probe(args) -> int:
    import time

    from apex_tpu.checkpoint import chaos
    from apex_tpu.serve import (EngineStalledError, EngineWatchdog,
                                PoisonedOutputError, ServeSLO,
                                build_flagship_engine,
                                validate_serve_report)

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    chaos.disarm_all()
    failures = []
    result = {"backend": "tpu" if on_tpu else "cpu"}

    # ---------------- unloaded baseline (the bitwise oracle) ----------
    eng0 = build_flagship_engine(on_tpu)
    n_slots = eng0.serve_cfg.n_slots
    n_requests = args.requests or 4 * n_slots       # the 4x storm size
    max_new = min(args.max_new or (8 if on_tpu else 6),
                  eng0.serve_cfg.max_new_cap)
    result.update(n_slots=n_slots, n_requests=n_requests,
                  max_new=max_new)
    _workload(eng0, n_requests, max_new)
    ref_fins = {}
    _drive(eng0, ref_fins)
    ref = {r: f.tokens for r, f in ref_fins.items()}
    if len(ref) != n_requests:
        failures.append("baseline did not finish every request")
    params = eng0.params

    # ---------------- leg 1: overload + deadlines + cancel ------------
    eng1 = build_flagship_engine(
        on_tpu, params=params,
        serve_overrides={"max_queue_depth": 2 * n_slots,
                         "shed_policy": "shed-lowest-deadline"})
    eng1.slo = ServeSLO(max_queue_wait_ms=args.slo_queue_wait_ms)
    # mixed deadlines: one seeded breach (expires in queue), a band of
    # long-but-finite ones (the shed-lowest-deadline policy's victim
    # pool), the rest unbounded.  The breach rides EARLY — before the
    # bounded queue fills — so it dies by EXPIRY at the next submit's
    # sweep (microseconds later), never by shed: the two negative
    # controls must fire separately, each by name.
    deadlines = [None] * n_requests
    for i in range(n_requests // 4, n_requests // 2):
        deadlines[i] = 60_000.0             # feasible everywhere
    breach_idx = n_requests // 8
    deadlines[breach_idx] = 0.002           # the seeded deadline breach
    rids1 = _workload(eng1, n_requests, max_new, deadlines=deadlines)
    shed_in_submit = eng1.telemetry.ledger.n_shed
    # cancel one queued + one live request mid-storm
    fins1 = {}
    eng1.step()
    live_rid = next(iter(eng1._live.values())).rid
    queued_rid = next((r.rid for r in eng1._pending
                       if r.deadline_t is None), None)
    assert eng1.cancel(live_rid), "live cancel refused"
    if queued_rid is not None and not eng1.cancel(queued_rid):
        failures.append("overload: queued cancel refused")
    time.sleep(0.01)                        # let the breach deadline pass
    _drive(eng1, fins1)
    led1 = eng1.telemetry.ledger
    _leg_checks("overload", eng1, fins1, ref, failures)
    result["overload"] = {
        "n_shed": led1.n_shed, "n_expired": led1.n_expired,
        "n_cancelled": led1.n_cancelled, "n_ok": led1.n_retired,
        "shed_at_submit": shed_in_submit,
    }
    # negative controls, BY NAME
    if fins1[rids1[breach_idx]].status != "expired":
        failures.append(
            f"overload: seeded deadline breach (rid "
            f"{rids1[breach_idx]}) ended "
            f"{fins1[rids1[breach_idx]].status!r}, expected 'expired'")
    if led1.n_expired < 1:
        failures.append("overload: no deadline expiry despite the "
                        "seeded breach — the TTL plane is not firing")
    if led1.n_shed < 1:
        failures.append("overload: 4x storm against a bounded queue "
                        "shed nothing — overload control is not firing")
    if fins1[live_rid].status != "cancelled":
        failures.append(
            f"overload: mid-generation cancel ended "
            f"{fins1[live_rid].status!r}, expected 'cancelled'")
    if queued_rid is not None and fins1[queued_rid].status != "cancelled":
        failures.append("overload: queued cancel did not end "
                        "'cancelled'")
    # policy ordering: with shed-lowest-deadline, no unbounded-deadline
    # request may be shed while a sooner-deadline one sat in the queue
    # at the same shed decision — verify the victims carry the
    # smallest deadlines among their shed cohort
    shed_rids = {r for r, f in fins1.items() if f.status == "shed"}
    tight = {rids1[i] for i in range(n_requests)
             if deadlines[i] is not None and i != breach_idx}
    if shed_rids and not (shed_rids & tight) and (tight - shed_rids):
        # every shed victim was deadline-less while deadline-carrying
        # requests queued: the lowest-deadline policy did not order
        failures.append("overload: shed-lowest-deadline shed only "
                        "deadline-less requests while deadline-carrying "
                        "ones were queued")

    # ---------------- leg 2: stall → watchdog trip → restart ----------
    chaos.disarm_all()
    eng2 = build_flagship_engine(on_tpu, params=params)
    _workload(eng2, min(n_requests, 2 * n_slots), max_new)
    dog = EngineWatchdog(eng2, stall_timeout_s=0.05, snapshot_every=1)
    chaos.arm("serve.stall_step", 4)
    fins2 = {}
    tripped = None
    steps = 0
    while eng2.pending:
        if steps >= _MAX_STEPS:
            failures.append("stall: drive loop exceeded bound")
            break
        eng2.step()
        for f in eng2.poll():
            fins2[f.request_id] = f
        try:
            dog.check()
        except EngineStalledError as e:
            tripped = e
            eng2 = dog.restart()
        if eng2.stalled:
            time.sleep(0.02)
        steps += 1
    eng2._retire_finished()
    for f in eng2.poll():
        fins2[f.request_id] = f
    if tripped is None:
        failures.append("stall: watchdog never tripped on the wedged "
                        "engine — the stall negative control failed")
    elif "stalled" not in str(tripped) or tripped.step is None:
        failures.append(f"stall: trip does not name the stuck step: "
                        f"{tripped}")
    _leg_checks("stall", eng2, fins2, ref, failures)
    result["stall"] = {"tripped": tripped is not None,
                       "stalls": dog.stalls, "restarts": dog.restarts,
                       "snapshot_step": dog.snapshot_step}

    # ---------------- leg 3: poisoned logits → detect → recover -------
    chaos.disarm_all()
    eng3 = build_flagship_engine(on_tpu, params=params)
    _workload(eng3, min(n_requests, 2 * n_slots), max_new)
    dog3 = EngineWatchdog(eng3, stall_timeout_s=30.0, snapshot_every=1)
    chaos.arm("serve.poison_logits", 3)
    fins3 = {}
    poisoned = None
    steps = attempts = 0
    while eng3.pending:
        if steps >= _MAX_STEPS:
            failures.append("poison: drive loop exceeded bound")
            break
        try:
            eng3.step()
        except PoisonedOutputError as e:
            poisoned = e
            attempts += 1
            if attempts > 2:
                failures.append("poison: restart did not clear the "
                                "corruption (snapshot not known-good)")
                break
            eng3 = dog3.restart()
            continue
        for f in eng3.poll():
            fins3[f.request_id] = f
        dog3.check()
        steps += 1
    eng3._retire_finished()
    for f in eng3.poll():
        fins3[f.request_id] = f
    if poisoned is None:
        failures.append("poison: garbage token ids were never "
                        "detected at the retire poll")
    elif poisoned.slot is None or "token ids outside" not in str(poisoned):
        failures.append(f"poison: detection does not name the "
                        f"slot/range: {poisoned}")
    _leg_checks("poison", eng3, fins3, ref, failures)
    result["poison"] = {"detected": poisoned is not None,
                        "restarts": dog3.restarts}

    # ---------------- leg 4: kill mid-drain → snapshot recovery -------
    chaos.disarm_all()
    kill_ok = True
    for count in (1, 3):
        eng4 = build_flagship_engine(on_tpu, params=params)
        _workload(eng4, min(n_requests, 2 * n_slots), max_new)
        fins4 = {}
        for _ in range(2):
            eng4.step()
            for f in eng4.poll():
                fins4[f.request_id] = f
        chaos.arm("serve.kill_mid_drain", count)
        try:
            eng4.drain(max_steps=_MAX_STEPS)
            failures.append(f"kill-drain[{count}]: armed kill never "
                            "fired")
            kill_ok = False
            continue
        except chaos.SimulatedPreemption:
            pass
        # the deploy died mid-drain; the snapshot contract recovers —
        # drain the replacement, then finish its queued tail in a
        # third engine from the DRAINED snapshot
        snap = eng4.state_dict()
        for f in eng4.poll():
            fins4[f.request_id] = f
        eng5 = build_flagship_engine(on_tpu, params=params)
        eng5.load_state_dict(snap)
        drained = eng5.drain(max_steps=_MAX_STEPS)
        for f in eng5.poll():
            fins4[f.request_id] = f
        eng6 = build_flagship_engine(on_tpu, params=params)
        eng6.load_state_dict(drained)
        _drive(eng6, fins4)
        ok = _leg_checks(f"kill-drain[{count}]", eng6, fins4, ref,
                         failures)
        kill_ok = kill_ok and len(fins4) == min(n_requests, 2 * n_slots)
        if len(ok) != len(fins4):
            failures.append(f"kill-drain[{count}]: drain lost a live "
                            "request to a non-ok terminal")
    result["kill_drain_ok"] = kill_ok

    # the overload leg's report must be dump-valid
    try:
        rep = eng1.telemetry_report()
        validate_serve_report(rep)
        json.dumps(rep)
    except (ValueError, TypeError) as e:
        failures.append(f"telemetry_report invalid: {e}")

    chaos.disarm_all()
    result["ok"] = not failures
    if args.json:
        print(json.dumps(result, sort_keys=True))
    else:
        for k in sorted(result):
            print(f"  {k}: {result[k]}")
    if failures:
        for f in failures:
            print(f"serve_chaos_probe: FAIL — {f}", file=sys.stderr)
        return 1
    print("serve_chaos_probe: OK (survivors bitwise at every fail "
          "point, pool reconciled, ledger balanced, negative controls "
          "fired by name, zero steady-state recompiles)")
    return 0


# ---------------------------------------------------------------------------
# fixture (re)generation — run once, commit the result
# ---------------------------------------------------------------------------

def write_fixture() -> int:
    import time

    from apex_tpu.serve import build_flagship_engine

    # a small real chaos run so the committed report carries every
    # terminal state: bounded queue + a doomed deadline + a cancel
    eng = build_flagship_engine(
        False, serve_overrides={"max_queue_depth": 4,
                                "shed_policy": "shed-lowest-deadline"})
    n = 3 * eng.serve_cfg.n_slots
    deadlines = [None] * n
    # early, before the bounded queue fills: dies by EXPIRY at the
    # next submit's sweep, not by shed (the probe-leg convention)
    deadlines[2] = 0.002
    rids = _workload(eng, n, 6, deadlines=deadlines)
    eng.step()
    eng.cancel(next(iter(eng._live.values())).rid)
    time.sleep(0.01)
    fins = {}
    _drive(eng, fins)
    fixture = {
        "_comment": "serve_chaos_probe --selftest fixture: a real "
                    "chaos smoke-run telemetry report (schema drift "
                    "gate; carries every terminal state) + the seeded "
                    "negative controls.  Regenerate with `python "
                    "scripts/serve_chaos_probe.py --write-fixture`.",
        "report": eng.telemetry_report(),
        "seeded_deadline_breach": {"deadline_ms": 5.0},
        "seeded_shed": {
            "policy": "shed-lowest-deadline",
            "candidates": [
                {"rid": 10, "deadline_t": 9.0},
                {"rid": 11, "deadline_t": 2.5},
                {"rid": 12},
                {"rid": 13, "deadline_t": 7.0},
            ],
            "expect_victim": 11,
        },
        "seeded_watchdog": {"stall_timeout_s": 4.0, "overshoot_s": 0.5},
    }
    with open(FIXTURE, "w") as f:
        json.dump(fixture, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {FIXTURE}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description="serving resilience chaos gate")
    ap.add_argument("--selftest", action="store_true",
                    help="fixture drift gate + seeded negative "
                         "controls; exit 1 on drift")
    ap.add_argument("--write-fixture", action="store_true",
                    help="regenerate scripts/serve_chaos_fixture.json")
    ap.add_argument("--requests", type=int, default=None,
                    help="storm size (default 4x slots)")
    ap.add_argument("--max-new", type=int, default=None,
                    help="per-request token budget cap "
                         "(default 6 CPU / 8 TPU)")
    ap.add_argument("--slo-queue-wait-ms", type=float,
                    default=240_000.0,
                    help="max queue wait SLO for the proactive-shed "
                         "projection (default generous for CI)")
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
