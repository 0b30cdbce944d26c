"""Serving resilience (ISSUE 14): per-request deadlines/TTL,
cancellation in-queue and mid-generation, bounded-queue overload
shedding (policy ordering + SLO-driven proactive shed), the ledger's
terminal states and exact balance identity, the PagedKVCache
double-release guard, the EngineWatchdog stall-trip/restart contract,
graceful drain, the SCHEMA v10 stamps, and the
`scripts/serve_chaos_probe.py` CI gates."""

import os
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.checkpoint import chaos
from apex_tpu.models.gpt import GPT, GPTConfig
from apex_tpu.serve import (
    DecodeEngine,
    EngineStalledError,
    EngineWatchdog,
    PageAccountingError,
    PagedKVCache,
    KVCacheConfig,
    PoisonedOutputError,
    RequestLedger,
    ServeConfig,
    ServeSLO,
    choose_shed_victim,
)

ROOT = pathlib.Path(__file__).resolve().parent.parent

_CFG = GPTConfig(vocab_size=64, seq_len=64, hidden=32, num_layers=2,
                 num_heads=4, dropout=0.0)
_SC = ServeConfig(n_slots=3, max_prompt_len=8, max_new_cap=8,
                  page_size=4)

_PROMPTS = [[5, 9, 2, 17], [33, 1], [40, 41, 42], [8, 9], [11, 12, 13],
            [21, 22], [7, 7, 7]]
_BUDGETS = [6, 8, 5, 4, 7, 3, 5]


@pytest.fixture(scope="module")
def params():
    p = GPT(_CFG).init(jax.random.PRNGKey(7))
    p["pos_embed"] = p["pos_embed"] * 20.0  # varied decode trajectories
    return p


@pytest.fixture(scope="module")
def ref_tokens(params):
    """The unloaded baseline every surviving request must match
    BITWISE (faults may kill requests, never change survivors)."""
    eng = DecodeEngine(_CFG, params, _SC)
    for p, b in zip(_PROMPTS, _BUDGETS):
        eng.submit(p, b)
    return {f.request_id: f.tokens for f in eng.run()}


@pytest.fixture(autouse=True)
def _disarm():
    chaos.disarm_all()
    yield
    chaos.disarm_all()


def _drive(eng, max_steps=400, sleep_when_stalled=0.0):
    fins = {}
    steps = 0
    while eng.pending:
        assert steps < max_steps, "drive loop exceeded bound"
        eng.step()
        for f in eng.poll():
            fins[f.request_id] = f
        if sleep_when_stalled and eng.stalled:
            time.sleep(sleep_when_stalled)
        steps += 1
    eng._retire_finished()
    for f in eng.poll():
        fins[f.request_id] = f
    return fins


def _assert_clean(eng, fins, ref):
    """Every leg's shared invariants: ok-survivors bitwise, pool fully
    reconciled, ledger balance identity closed."""
    for rid, f in fins.items():
        if f.status == "ok":
            assert f.tokens == ref[rid], f"request {rid} drifted"
    assert eng.cache.free_pages == eng.kv_config.usable_pages
    if eng.telemetry is not None:
        assert eng.telemetry.ledger.balance()["ok"], \
            eng.telemetry.ledger.balance()


# ------------------------------------------------------------------
# deadlines / TTL
# ------------------------------------------------------------------


def test_deadline_expires_in_queue(params, ref_tokens):
    """A queued request whose TTL passes is evicted at the admit sweep
    (terminal `expired`, where='queue', no pages ever reserved) and
    the survivors decode bitwise."""
    eng = DecodeEngine(_CFG, params, _SC)
    rids = [eng.submit(p, b) for p, b in zip(_PROMPTS[:3], _BUDGETS[:3])]
    doomed = eng.submit([1, 2, 3], 4, deadline_ms=0.001)
    time.sleep(0.005)
    fins = _drive(eng)
    assert fins[doomed].status == "expired"
    assert fins[doomed].tokens == []
    led = eng.telemetry.ledger
    assert led.n_expired_queue == 1 and led.n_expired_live == 0
    rec = {r.request_id: r for r in led.tail}[doomed]
    assert rec.status == "expired" and rec.where == "queue"
    assert rec.admit_t is None                 # never admitted
    assert rec.deadline_ms == 0.001
    # expiry never fed the latency estimators
    assert led.ttft.n == 3 and led.queue_wait.n == 3
    for rid in rids:
        assert fins[rid].status == "ok"
    _assert_clean(eng, fins, ref_tokens)


def test_deadline_evicts_live_slot(params, ref_tokens):
    """A LIVE request past its deadline is evicted at the retire poll:
    pages released mid-generation, partial tokens noted, terminal
    `expired` where='live' — and no other stream is disturbed."""
    eng = DecodeEngine(_CFG, params, _SC)
    doomed = eng.submit(_PROMPTS[0], _BUDGETS[0], deadline_ms=25.0)
    other = eng.submit(_PROMPTS[1], _BUDGETS[1])
    eng.step()                                  # both admitted, decoding
    assert any(r.rid == doomed for r in eng._live.values())
    pages_live = eng.cache.free_pages
    time.sleep(0.05)                            # deadline passes mid-gen
    fins = _drive(eng)
    assert fins[doomed].status == "expired"
    led = eng.telemetry.ledger
    assert led.n_expired_live == 1
    rec = {r.request_id: r for r in led.tail}[doomed]
    assert rec.where == "live" and rec.admit_t is not None
    assert fins[other].status == "ok"
    assert fins[other].tokens == ref_tokens[other]
    assert eng.cache.free_pages > pages_live    # pages came back
    _assert_clean(eng, fins, ref_tokens)


def test_submit_validates_deadline(params):
    eng = DecodeEngine(_CFG, params, _SC)
    with pytest.raises(ValueError, match="deadline_ms"):
        eng.submit([1, 2], 4, deadline_ms=0.0)
    with pytest.raises(ValueError, match="deadline_ms"):
        eng.submit([1, 2], 4, deadline_ms=-5.0)


# ------------------------------------------------------------------
# cancellation
# ------------------------------------------------------------------


def test_cancel_in_queue_and_mid_generation(params, ref_tokens):
    """cancel() removes a queued request outright and ends a live one
    through the done mask (next retire poll, partial tokens, pages
    released); unknown/terminal ids return False; survivors bitwise;
    zero steady recompiles (the done-mask edit is a VALUE edit)."""
    eng = DecodeEngine(_CFG, params, _SC)
    rids = [eng.submit(p, b) for p, b in zip(_PROMPTS, _BUDGETS)]
    assert eng.cancel(rids[4])                  # still queued
    eng.step()
    live_rid = next(iter(eng._live.values())).rid
    assert eng.cancel(live_rid)                 # mid-generation
    assert not eng.cancel(live_rid)             # double-cancel: no-op
    assert not eng.cancel(10_000)               # unknown id
    fins = _drive(eng)
    assert fins[rids[4]].status == "cancelled"
    assert fins[rids[4]].tokens == []
    assert fins[live_rid].status == "cancelled"
    led = eng.telemetry.ledger
    assert led.n_cancelled_queue == 1 and led.n_cancelled_live == 1
    # a cancelled live request keeps its partial generation (info only)
    rec = {r.request_id: r for r in led.tail}[live_rid]
    assert rec.status == "cancelled" and rec.where == "live"
    assert eng.recompile_ok
    _assert_clean(eng, fins, ref_tokens)


# ------------------------------------------------------------------
# overload control
# ------------------------------------------------------------------


def test_bounded_queue_sheds_newest(params, ref_tokens):
    """shed-newest at capacity: the incoming request is the victim,
    `last_shed_rid` surfaces the signal through submit(), the
    saturation gauge reads 1.0, and the ledger counts every shed."""
    sc = ServeConfig(n_slots=3, max_prompt_len=8, max_new_cap=8,
                     page_size=4, max_queue_depth=2)
    eng = DecodeEngine(_CFG, params, sc)
    kept = [eng.submit(_PROMPTS[0], _BUDGETS[0])]
    assert eng.last_shed_rid is None and not eng.overloaded
    kept.append(eng.submit(_PROMPTS[1], _BUDGETS[1]))
    assert eng.last_shed_rid is None
    assert eng.gauges()["queue_saturation"] == 1.0
    assert eng.overloaded
    shed = eng.submit(_PROMPTS[2], _BUDGETS[2])
    assert eng.last_shed_rid == shed
    fins = {f.request_id: f for f in eng.poll()}
    assert fins[shed].status == "shed" and fins[shed].tokens == []
    assert eng.telemetry.ledger.n_shed == 1
    fins.update(_drive(eng))
    for rid in kept:
        assert fins[rid].status == "ok"
    _assert_clean(eng, fins, ref_tokens)


def test_shed_lowest_deadline_policy_ordering(params):
    """shed-lowest-deadline sheds the earliest-deadline candidate
    (least slack = least feasible work wasted); deadline-less requests
    go last.  Checked through the engine AND the pure spelling the
    chaos probe's selftest replays."""

    class _C:
        def __init__(self, rid, deadline_t):
            self.rid, self.deadline_t = rid, deadline_t

    cands = [_C(0, 9.0), _C(1, 2.5), _C(2, None), _C(3, 7.0)]
    assert choose_shed_victim(cands, "shed-lowest-deadline").rid == 1
    assert choose_shed_victim(cands, "shed-newest").rid == 3
    assert choose_shed_victim([_C(0, None), _C(1, None)],
                              "shed-lowest-deadline").rid == 1  # FIFO tilt
    with pytest.raises(ValueError, match="shed policy"):
        choose_shed_victim(cands, "shed-oldest")

    sc = ServeConfig(n_slots=3, max_prompt_len=8, max_new_cap=8,
                     page_size=4, max_queue_depth=3,
                     shed_policy="shed-lowest-deadline")
    eng = DecodeEngine(_CFG, params, sc)
    r_far = eng.submit([1, 2], 4, deadline_ms=90_000.0)
    r_soon = eng.submit([3, 4], 4, deadline_ms=10_000.0)
    r_none = eng.submit([5, 6], 4)
    r_in = eng.submit([7, 8], 4, deadline_ms=50_000.0)  # queue full now
    # victim = r_soon (earliest deadline), NOT the incoming request
    assert eng.last_shed_rid == r_soon
    statuses = {f.request_id: f.status for f in eng.poll()}
    assert statuses == {r_soon: "shed"}
    assert {r.rid for r in eng._pending} == {r_far, r_none, r_in}


def test_slo_projection_sheds_before_breach(params):
    """With ServeSLO(max_queue_wait_ms=) attached, the engine sheds
    when the PROJECTED wait (depth x mean service / slots) would
    breach — before the queue-wait plane does.  Seeded service
    samples make the projection deterministic."""
    eng = DecodeEngine(_CFG, params, _SC)
    eng.slo = ServeSLO(max_queue_wait_ms=100.0)
    # no service data yet: the projection never guesses
    assert eng.projected_queue_wait_s() is None
    r0 = eng.submit([1, 2], 4)
    assert eng.last_shed_rid is None
    # seed the service estimator: 0.2 s per request, 3 slots → each
    # queued request projects 0.2/3 s ≈ 66.7 ms of added wait
    for _ in range(4):
        eng.telemetry.ledger.service.add(0.2)
    r1 = eng.submit([3, 4], 4)        # depth 1 → proj 66.7ms < 100ms
    assert eng.last_shed_rid is None
    r2 = eng.submit([5, 6], 4)        # depth 2 → proj 133ms > 100ms: shed
    assert eng.last_shed_rid == r2
    assert eng.telemetry.ledger.n_shed == 1
    assert eng.overloaded             # the standing backpressure signal


def test_overload_storm_4x_mixed_deadlines(params, ref_tokens):
    """The satellite churn test: 4x slot capacity, bounded queue,
    mixed deadlines — shed-policy ordering holds, zero page leaks
    after the storm, and every surviving output is bitwise equal to
    the uncontended run."""
    sc = ServeConfig(n_slots=3, max_prompt_len=8, max_new_cap=8,
                     page_size=4, max_queue_depth=4,
                     shed_policy="shed-lowest-deadline")
    eng = DecodeEngine(_CFG, params, sc)
    # the full 7-request workload (vs 3 slots, pool-capped at 2 live)
    # + 5 filler requests = 4x capacity, half with finite deadlines
    rids, deadline_rids = [], []
    for i, (p, b) in enumerate(zip(_PROMPTS, _BUDGETS)):
        dl = 120_000.0 if i % 2 else None
        rids.append(eng.submit(p, b, deadline_ms=dl))
        if dl is not None:
            deadline_rids.append(rids[-1])
    extra = [eng.submit([9, 9 + i], 3, deadline_ms=120_000.0)
             for i in range(5)]
    led = eng.telemetry.ledger
    assert led.n_shed > 0, "4x storm shed nothing"
    # policy ordering: with every queued deadline equal, victims are
    # the NEWEST deadline-carrying candidates; deadline-less queued
    # requests survive shedding entirely
    shed = {f.request_id for f in eng.poll() if f.status == "shed"}
    assert shed and shed <= set(deadline_rids) | set(extra)
    fins = _drive(eng)
    for f in fins.values():
        assert f.status in ("ok", "shed")
    _assert_clean(eng, fins, ref_tokens)
    assert eng.recompile_ok
    bal = led.balance()
    assert bal["ok"] and bal["n_shed"] == len(shed)


# ------------------------------------------------------------------
# ledger terminal states: exact reconciliation (satellite)
# ------------------------------------------------------------------


def test_terminal_states_reconcile_against_step_sums(params):
    """Lifetime counters balance EXACTLY against step()'s (admitted,
    retired) sums plus the terminal counts: every vacated slot is a
    normal retire, a live expiry, or a live cancel — and every
    submission is admitted, queue-terminal, or still open."""
    eng = DecodeEngine(_CFG, params, _SC)
    rids = [eng.submit(p, b, deadline_ms=(30.0 if i == 5 else None))
            for i, (p, b) in enumerate(zip(_PROMPTS, _BUDGETS))]
    eng.cancel(rids[6])                        # queue-side cancel
    hand_admitted = hand_retired = 0
    a, r = eng.step()
    hand_admitted += a
    hand_retired += r
    eng.cancel(next(iter(eng._live.values())).rid)   # live cancel
    time.sleep(0.05)                           # rid 5's deadline passes
    steps = 0
    while eng.pending:
        a, r = eng.step()
        hand_admitted += a
        hand_retired += r
        eng.poll()
        steps += 1
        assert steps < 400
    hand_retired += eng._retire_finished()
    led = eng.telemetry.ledger
    # slot exits == step() retire sums (normal + live-cancel + expiry)
    assert (led.n_retired + led.n_cancelled_live + led.n_expired_live
            == hand_retired)
    # admissions == step() admit sums
    assert led.n_admitted == hand_admitted
    # the submission identity
    assert (led.n_submitted == led.n_retired + led.n_expired
            + led.n_cancelled + led.n_shed + led.n_open)
    assert led.n_open == 0
    assert led.balance()["ok"]


def test_restored_requests_keep_original_submit_stamps(params):
    """ISSUE 14 satellite: the snapshot preserves submit AGE, so a
    restored request's ledger record keeps its original submit stamp
    (queue wait spans the preemption) and a live deadline keeps
    counting down instead of resetting."""
    eng = DecodeEngine(_CFG, params, _SC)
    for i in range(5):
        eng.submit([i + 1, i + 2], 6,
                   deadline_ms=(90_000.0 if i == 4 else None))
    eng.step()
    time.sleep(0.02)
    snap = eng.state_dict()
    ages = {e[0]: e[3] for e in snap["scheduler"]["pending"]}
    assert all(a >= 0.02 for a in ages.values())      # real ages
    eng2 = DecodeEngine(_CFG, params, _SC)
    t_restore = time.perf_counter()
    eng2.load_state_dict(snap)
    led2 = eng2.telemetry.ledger
    for req in eng2._pending:
        rec = led2._open[req.rid]
        # original stamp: age-adjusted to BEFORE the restore moment
        # (a fresh re-stamp would land after t_restore)
        assert rec.submit_t < t_restore
        if req.deadline_ms is not None:
            # remaining deadline re-absolutized, not reset: strictly
            # less than a fresh 90 s TTL from the restore point
            assert req.deadline_t < t_restore + 90.0
    fins = _drive(eng2)
    assert all(f.status == "ok" for f in fins.values())
    # the restored queued cohort's queue waits INCLUDE pre-snapshot
    # time (>= the sleep), proving the stamps survived
    waits = [r.queue_wait_s for r in led2.tail
             if not r.restored and r.queue_wait_s]
    assert waits and min(waits) >= 0.015
    assert led2.balance()["ok"]


def test_v1_snapshot_refused_by_version(params):
    eng = DecodeEngine(_CFG, params, _SC)
    snap = eng.state_dict()
    snap["serve_state_version"] = 1
    eng2 = DecodeEngine(_CFG, params, _SC)
    with pytest.raises(ValueError, match="serve_state_version"):
        eng2.load_state_dict(snap)


# ------------------------------------------------------------------
# PagedKVCache double-release (satellite)
# ------------------------------------------------------------------


def test_double_release_raises_by_name():
    """release_slot on an already-freed or never-allocated slot raises
    PageAccountingError instead of silently corrupting the free list;
    accounting stays exact through the failure."""
    cfg = KVCacheConfig(n_layers=1, n_kv_heads=2, head_dim=8,
                        n_slots=4, n_pages=9, pages_per_slot_max=4,
                        page_size=4)
    cache = PagedKVCache(cfg)
    assert cache.allocate_slot(0, 10) is not None     # 3 pages
    assert cache.allocate_slot(1, 4) is not None      # 1 page
    cache.release_slot(0)
    with pytest.raises(PageAccountingError, match="double release"):
        cache.release_slot(0)                          # double free
    with pytest.raises(PageAccountingError, match="never allocated"):
        cache.release_slot(3)                          # never allocated
    # the free list survived both refusals intact: no page lost, none
    # duplicated (the regression the silent path would have hidden)
    cache.release_slot(1)
    assert sorted(cache._free) == list(range(1, 9))
    assert cache.free_pages == cfg.usable_pages


# ------------------------------------------------------------------
# watchdog + poison + drain
# ------------------------------------------------------------------


def test_watchdog_trips_restarts_bitwise(params, ref_tokens):
    """The serve.stall_step wedge: the watchdog trips by name
    (naming the stuck step), dumps nothing silently, restart()
    resumes from the periodic snapshot and the finished tokens are
    BITWISE the unstalled run's; counters stamp into serve_record."""
    eng = DecodeEngine(_CFG, params, _SC)
    for p, b in zip(_PROMPTS[:5], _BUDGETS[:5]):
        eng.submit(p, b)
    dog = EngineWatchdog(eng, stall_timeout_s=0.05, snapshot_every=1)
    chaos.arm("serve.stall_step", 3)
    fins = {}
    tripped = None
    steps = 0
    while eng.pending:
        assert steps < 400
        eng.step()
        for f in eng.poll():
            fins[f.request_id] = f
        try:
            dog.check()
        except EngineStalledError as e:
            tripped = e
            eng = dog.restart()
        if eng.stalled:
            time.sleep(0.02)
        steps += 1
    eng._retire_finished()
    for f in eng.poll():
        fins[f.request_id] = f
    assert tripped is not None and tripped.step is not None
    assert "stalled" in str(tripped) and f"step {tripped.step}" in str(
        tripped)
    assert dog.stalls == 1 and dog.restarts == 1
    assert all(f.status == "ok" for f in fins.values())
    _assert_clean(eng, fins, ref_tokens)
    rec = eng.serve_record()
    assert rec["serve_watchdog_stalls"] == 1
    assert rec["serve_watchdog_restarts"] == 1


def test_watchdog_idle_engine_never_trips(params):
    """No pending work is not a stall: the clock re-arms while idle
    and after new submissions the timeout is judged fresh."""
    eng = DecodeEngine(_CFG, params, _SC)
    t = [0.0]
    dog = EngineWatchdog(eng, stall_timeout_s=1.0, clock=lambda: t[0])
    t[0] = 50.0
    dog.check()                                # idle: no trip
    eng.submit([1, 2], 2)
    t[0] = 50.5
    dog.check()                                # within timeout: fine
    t[0] = 52.0
    with pytest.raises(EngineStalledError):
        dog.check()


def test_poison_detected_and_snapshot_stays_good(params, ref_tokens):
    """serve.poison_logits: garbage token ids are refused BY NAME at
    the retire poll, and the watchdog's snapshot is last-KNOWN-GOOD
    (a poisoned candidate never replaces it), so one restart clears
    the corruption and the run finishes bitwise."""
    eng = DecodeEngine(_CFG, params, _SC)
    for p, b in zip(_PROMPTS[:4], _BUDGETS[:4]):
        eng.submit(p, b)
    dog = EngineWatchdog(eng, stall_timeout_s=30.0, snapshot_every=1)
    chaos.arm("serve.poison_logits", 2)
    fins = {}
    caught = None
    steps = restarts = 0
    while eng.pending:
        assert steps < 400
        try:
            eng.step()
        except PoisonedOutputError as e:
            caught = e
            restarts += 1
            assert restarts < 3, "snapshot was not known-good"
            eng = dog.restart()
            continue
        for f in eng.poll():
            fins[f.request_id] = f
        dog.check()
        steps += 1
    eng._retire_finished()
    for f in eng.poll():
        fins[f.request_id] = f
    assert caught is not None and caught.slot is not None
    assert "token ids outside" in str(caught)
    assert all(f.status == "ok" for f in fins.values())
    _assert_clean(eng, fins, ref_tokens)


def test_drain_finishes_live_snapshots_queue(params, ref_tokens):
    """drain(): admission stops (submit refuses), live slots finish,
    the snapshot carries the queued remainder, and a fresh engine of
    the same deployment completes them bitwise.  kill_mid_drain dies
    by SimulatedPreemption and the snapshot contract recovers."""
    eng = DecodeEngine(_CFG, params, _SC)
    for p, b in zip(_PROMPTS[:5], _BUDGETS[:5]):
        eng.submit(p, b)
    eng.step()
    n_queued = len(eng._pending)
    assert n_queued > 0
    snap = eng.drain()
    with pytest.raises(RuntimeError, match="drain"):
        # admission is stopped DURING drain; after it the engine is
        # reusable — check the guard via the draining flag path
        eng._draining = True
        eng.submit([1], 1)
    eng._draining = False
    assert len(eng._live) == 0
    assert len(snap["scheduler"]["pending"]) == n_queued
    fins = {f.request_id: f for f in eng.poll()}
    eng2 = DecodeEngine(_CFG, params, _SC)
    eng2.load_state_dict(snap)
    fins.update(_drive(eng2))
    assert set(fins) == set(range(5))
    assert all(f.status == "ok" for f in fins.values())
    _assert_clean(eng2, fins, ref_tokens)

    # the kill: drain dies partway, state_dict recovers
    eng3 = DecodeEngine(_CFG, params, _SC)
    for p, b in zip(_PROMPTS[:5], _BUDGETS[:5]):
        eng3.submit(p, b)
    eng3.step()
    chaos.arm("serve.kill_mid_drain", 2)
    with pytest.raises(chaos.SimulatedPreemption):
        eng3.drain()
    assert not eng3.draining                   # flag reset on the way out
    snap3 = eng3.state_dict()
    fins3 = {f.request_id: f for f in eng3.poll()}
    eng4 = DecodeEngine(_CFG, params, _SC)
    eng4.load_state_dict(snap3)
    fins3.update(_drive(eng4))
    assert all(f.status == "ok" for f in fins3.values())
    _assert_clean(eng4, fins3, ref_tokens)


# ------------------------------------------------------------------
# SCHEMA v10 stamps
# ------------------------------------------------------------------


def test_schema_v10_resilience_stamps_validate(params, tmp_path):
    """The terminal counters ride serve_record() always; watchdog
    counters once a watchdog attaches; a MetricsLogger(serve=) record
    carrying all of them validates under SCHEMA v10."""
    assert monitor.SCHEMA_VERSION >= 10
    eng = DecodeEngine(_CFG, params, _SC)
    doomed = eng.submit([1, 2], 4, deadline_ms=0.001)
    eng.submit([3, 4], 3)
    time.sleep(0.005)
    _drive(eng)
    EngineWatchdog(eng, stall_timeout_s=30.0)
    rec = eng.serve_record()
    assert rec["serve_expired_total"] == 1
    assert rec["serve_shed_total"] == 0
    assert rec["serve_cancelled_total"] == 0
    assert rec["serve_watchdog_stalls"] == 0
    assert rec["serve_watchdog_restarts"] == 0
    base = {
        "monitor_schema_version": monitor.SCHEMA_VERSION, "step": 1,
        "loss": 1.0, "grad_norm": 1.0, "param_norm": 1.0,
        "update_norm": 0.1, "loss_scale": 1.0, "overflow_count": 0,
        "skipped_steps": 0, "tokens_seen": 10.0, "step_time_ms": 1.0,
        "tokens_per_sec": 10.0, "mfu": 0.1,
        "serve_shed_fraction": 0.25,
        "serve_goodput_tokens_per_sec": 123.4,
    }
    base.update(rec)
    monitor.validate_record(base)
    # the reserved-prefix rule still bites: a null terminal counter is
    # a schema violation, not a missing sample
    with pytest.raises(ValueError):
        monitor.validate_record(dict(base, serve_shed_total=None))
    _ = doomed


# ------------------------------------------------------------------
# the standing CI gates (scripts/serve_chaos_probe.py)
# ------------------------------------------------------------------


def _run_script(path, *args, timeout=600):
    return subprocess.run(
        [sys.executable, str(path), *args], capture_output=True,
        text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_serve_chaos_probe_selftest():
    """Tier-1 gate (the slo_probe convention): fixture drift + the
    seeded deadline-breach / shed-ordering / watchdog-trip negative
    controls, all asserted by name."""
    r = _run_script(ROOT / "scripts" / "serve_chaos_probe.py",
                    "--selftest")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "serve_chaos_probe --selftest: OK" in r.stdout


def test_serve_chaos_probe_full_matrix():
    """The full overload + kill matrix on the flagship build path:
    survivors bitwise at every fail point, pool reconciled, ledger
    balanced, negative controls by name, zero steady recompiles."""
    r = _run_script(ROOT / "scripts" / "serve_chaos_probe.py",
                    "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    import json as _json

    # the JSON rides one line; the OK banner follows it (reverse-scan)
    line = next(ln for ln in reversed(r.stdout.strip().splitlines())
                if ln.startswith("{"))
    out = _json.loads(line)
    assert out["ok"] is True
    assert out["stall"]["tripped"] and out["poison"]["detected"]
    assert out["kill_drain_ok"]
    assert out["overload"]["n_shed"] > 0
    assert out["overload"]["n_expired"] > 0
