"""Fleet fault-tolerance CI gate (ISSUE 11): multi-host checkpoint
commit kill matrix + elastic-resume orchestration, end to end.

usage:
  python scripts/fleet_probe.py             # full probe (kill matrix)
  python scripts/fleet_probe.py --smoke     # tier-1 subset (bounded)
  python scripts/fleet_probe.py --selftest  # fixture drift gate
  python scripts/fleet_probe.py --json      # machine-readable result

The full probe launches REAL multi-process fleets through
`apex_tpu.parallel.multiproc` (2 controller processes × 4 emulated CPU
devices), kills a child AT each chaos fail point, and asserts the
commit protocol + orchestrator hold their contracts:

  1. BASELINE   — a 2-host fleet trains `--steps` steps (every host
                  computes the identical deterministic dp=4 step; each
                  host WRITES only its own ranks' shards), committing
                  a multi-host checkpoint at `--save-at` through the
                  sub-manifest → rank-0 barrier protocol.  The two
                  hosts' loss/canonical results must agree BITWISE —
                  the free cross-host consistency check.
  2. KILL MATRIX — one fleet per fail point (`ckpt.mid_shards` = shard
                  write, `host.before_submanifest`,
                  `host.before_barrier`, `rank.lost_at_step`): a
                  specific host really dies (os._exit, no cleanup) at
                  that point during a LATER save.  Afterward the
                  shared directory's `latest_committed_step` must
                  still be `--save-at` on every survivor, the commit
                  must `verify_shards`-load, and a surviving process 0
                  must have REFUSED the torn commit with the dead host
                  named (the barrier timeout path).
  3. RESUME     — `ElasticOrchestrator` resumes the baseline commit:
                  equal topology (dp=4) is BITWISE on losses and the
                  canonical master flat; a watchdog-driven lost rank
                  mid-segment triggers the full detect → dump →
                  rebuild at dp=2 → re-shard restore → resume cycle,
                  allclose at the resume_probe tolerances, with the
                  flight dump naming the last committed step,
                  `fleet_resumes == 1`, and ZERO steady-state
                  recompiles after either resume (RecompileSentry).
  4. NEGATIVE   — a seeded truncated shard inside the committed step
                  must be refused with the damaged rank NAMED (the
                  gate's own teeth), and the orchestrator on a
                  checkpoint-free directory must ESCALATE by name.

CPU-backend honesty: jax cannot run cross-process collectives on the
CPU backend (XLA: "Multiprocess computations aren't implemented"), so
each emulated host replicates the identical deterministic compute and
the probe distributes the STORAGE plane — per-host shard writes,
sub-manifests, the rank-0 commit barrier, and real process deaths —
which is exactly the layer `checkpoint.multihost` owns and a real TPU
pod would exercise with sharded compute.  On TPU hardware run the
probe with `--backend tpu` on a multi-host slice.

`--selftest` is the tier-1 fixture-drift gate (mirrors
`resume_probe.py --selftest`): the committed fixture
(scripts/fleet_fixture.json: a global manifest + the two sub-manifests
it was merged from) must still validate and re-merge to the same
global fields, and a one-host-missing barrier must be REFUSED with the
absent host named — the selftest's negative control.
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
# the orchestrator half needs dp up to 4 in THIS process: force an
# 8-way virtual mesh on CPU (must precede the first jax import)
if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fleet_fixture.json")
KILLED_RC = 77          # a chaos-killed worker's exit code


class _SkipToReport(Exception):
    """Abandon the remaining probe sections but still print the
    collected failures (a missing prerequisite, not a new finding)."""


# ---------------------------------------------------------------------------
# selftest (tier-1, no jax import)
# ---------------------------------------------------------------------------

def selftest() -> int:
    import shutil
    import tempfile

    import numpy as np

    from apex_tpu.checkpoint import multihost as MH
    from apex_tpu.checkpoint import validate_manifest
    from apex_tpu.checkpoint import sharded as S

    with open(FIXTURE) as f:
        fixture = json.load(f)
    try:
        validate_manifest(fixture["global"])
    except S.CheckpointError as e:
        print(f"fleet_probe --selftest: SCHEMA DRIFT — {e}",
              file=sys.stderr)
        print("(bump-side change? regenerate scripts/fleet_fixture.json "
              "with the new manifest schema)", file=sys.stderr)
        return 1

    # merge math: the committed sub-manifests must still merge to the
    # committed global manifest's fields (rank coverage, dtypes, files)
    merged = MH.merge_submanifests(
        fixture["submanifests"], step=fixture["global"]["step"],
        flat_layout=fixture["global"]["flat_layout"],
        scaler=fixture["global"]["scaler"])
    if merged["fields"] != fixture["global"]["fields"]:
        print("fleet_probe --selftest: sub-manifest merge no longer "
              "reproduces the committed global manifest's fields",
              file=sys.stderr)
        return 1

    # rank-coverage teeth: dropping one host must be refused naming
    # the missing ranks
    try:
        MH.merge_submanifests(fixture["submanifests"][:1],
                              step=fixture["global"]["step"],
                              flat_layout=fixture["global"]["flat_layout"])
    except MH.MultihostCommitError as e:
        if "missing" not in str(e):
            print("fleet_probe --selftest: one-host merge refusal lost "
                  f"its missing-rank naming: {e}", file=sys.stderr)
            return 1
    else:
        print("fleet_probe --selftest: merging HALF the fleet was NOT "
              "refused — rank coverage lost its teeth", file=sys.stderr)
        return 1

    # negative control: a barrier over a directory where host 1 never
    # published must time out REFUSING, with host 1 named
    tmp = tempfile.mkdtemp(prefix="fleet_probe_selftest_")
    try:
        d = S.step_dir(tmp, 3)
        sub = MH.write_host_shards(
            d, 3,
            {"params_shard": ("sharded",
                              {0: np.arange(4, dtype=np.float32)})},
            host=0, num_processes=2)
        MH.publish_submanifest(d, sub)
        try:
            MH.gather_submanifests(d, 2, step=3, timeout_s=0.2,
                                   poll_s=0.02)
        except MH.MultihostCommitError as e:
            if "host 1" not in str(e) or "refusing to commit" not in str(e):
                print("fleet_probe --selftest: barrier refusal lost its "
                      f"host naming: {e}", file=sys.stderr)
                return 1
        else:
            print("fleet_probe --selftest: a HALF-PUBLISHED step was "
                  "committed — the barrier lost its teeth",
                  file=sys.stderr)
            return 1
        if os.path.exists(os.path.join(d, S.MANIFEST)):
            print("fleet_probe --selftest: refusal left a manifest "
                  "behind", file=sys.stderr)
            return 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print("fleet_probe --selftest: OK")
    return 0


# ---------------------------------------------------------------------------
# shared training segment (worker fleet AND in-process orchestrator)
# ---------------------------------------------------------------------------

def _make_batches(n_steps, batch, seq, vocab):
    import numpy as np
    rng = np.random.RandomState(4321)
    out = []
    for _ in range(n_steps):
        t = rng.randint(0, vocab, size=(batch, seq)).astype(np.int32)
        out.append((t, np.roll(t, -1, axis=1)))
    return out


def _config():
    from apex_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=64, seq_len=16, hidden=32,
                     num_layers=2, num_heads=2, dropout=0.0), 8


def _build_segment(dp, ckpt_dir, *, resume_step=None, manager_kw=None):
    """Fresh dp-way ZeRO-2 GPT train step + CheckpointManager (resumed
    from `resume_step` when given).  Returns a dict of live pieces —
    the worker and the orchestrator sessions drive it differently."""
    import jax
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.checkpoint import CheckpointManager
    from apex_tpu.monitor.compile import RecompileSentry
    from apex_tpu.optimizers.distributed_fused_adam import (
        DistributedFusedAdam,
    )
    from apex_tpu.parallel import ddp
    from apex_tpu.parallel import mesh as M
    from apex_tpu.models.gpt import GPT
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    cfg, batch = _config()
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:dp])
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    amp_state = amp.initialize(opt_level="O0", loss_scale="dynamic")
    scaler = amp_state.loss_scalers[0]
    opt = DistributedFusedAdam(num_shards=dp, lr=1e-2, n_buckets=2,
                               use_pallas=False)
    sspec = opt.state_partition_specs()
    state = jax.jit(shard_map(opt.init, mesh=mesh, in_specs=(P(),),
                              out_specs=sspec, check_vma=False))(params)
    manager = CheckpointManager(ckpt_dir, opt, every_n_steps=1, keep=8,
                                **(manager_kw or {}))
    if resume_step is not None:
        state, restored_scaler, _ = manager.restore(mesh,
                                                    step=resume_step)
        if restored_scaler is not None:
            scaler = restored_scaler
    step = ddp.make_train_step(
        lambda p, b: model.loss(p, b[0], b[1]), opt, mesh,
        amp_state=amp_state, batch_spec=(P("dp"), P("dp")))
    sentry = RecompileSentry(step, name=f"fleet_probe_dp{dp}",
                             warn=False)
    return {"mesh": mesh, "opt": opt, "manager": manager,
            "sentry": sentry, "state": state, "scaler": scaler,
            "batch": batch, "cfg": cfg, "np": np}


def _canonical(seg):
    import numpy as np

    from apex_tpu.checkpoint import sharded as S
    glob = np.asarray(seg["state"].params_shard)
    return S.canonical_flat(list(np.split(glob, seg["opt"].num_shards)),
                            seg["opt"].shard_layout())


def _drive(seg, batches, start, stop, *, save_at=(), kill_save=None,
           on_step=None):
    """Run steps [start, stop); save (multihost-aware) on the listed
    steps.  `kill_save`: arm APEX_TPU_CHAOS_SAVE's fail points right
    before saving that step.  `on_step(i)` runs before each step (the
    orchestrator feeds its watchdog there).  Returns (losses,
    steady_recompiles, refusal-or-None)."""
    import numpy as np

    from apex_tpu.checkpoint import MultihostCommitError, chaos

    sentry, manager = seg["sentry"], seg["manager"]
    state, scaler = seg["state"], seg["scaler"]
    losses, calls, refusal = [], 0, None
    for i in range(start, stop):
        if on_step is not None:
            on_step(i)
        chaos.check("rank.lost_at_step")
        t, l = batches[i]
        state, scaler, loss = sentry(state, scaler, (t, l))
        calls += 1
        if calls == 2:
            _ = np.asarray(loss)
            sentry.mark_steady()
        losses.append(float(np.asarray(loss, np.float32)))
        if (i + 1) in save_at or (i + 1) == kill_save:
            if (i + 1) == kill_save:
                chaos.arm_from_env(var="APEX_TPU_CHAOS_SAVE")
            try:
                manager.save(i + 1, state, scaler,
                             model_state={"rng_key": np.asarray(
                                 [7, i + 1], np.uint32)})
                manager.wait()
            except MultihostCommitError as e:
                refusal = str(e)  # survivor refused a torn commit —
                # correct behavior; training would continue
    if calls == 1:
        sentry.mark_steady()
    seg["state"], seg["scaler"] = state, scaler
    return losses, int(sentry.steady_recompiles), refusal


# ---------------------------------------------------------------------------
# worker mode (one emulated host, spawned via parallel/multiproc)
# ---------------------------------------------------------------------------

def worker(args) -> int:
    import numpy as np

    from apex_tpu.checkpoint import chaos
    from apex_tpu.checkpoint.chaos import SimulatedPreemption
    from apex_tpu.parallel import mesh as M

    pid = int(os.environ.get("APEX_TPU_PROCESS_ID", "0"))
    nproc = int(os.environ.get("APEX_TPU_NUM_PROCESSES", "1"))
    chaos.arm_from_env()  # rank.lost_at_step fires mid-training
    cfg, batch = _config()
    batches = _make_batches(args.steps, batch, cfg.seq_len,
                            cfg.vocab_size)
    result = {"proc": pid, "nproc": nproc}
    try:
        seg = _build_segment(
            args.dp, args.ckpt_dir,
            manager_kw=dict(process_id=pid, num_processes=nproc,
                            async_write=False,
                            attempt=args.attempt,
                            barrier_timeout_s=args.barrier_timeout))
        losses, retraces, refusal = _drive(
            seg, batches, 0, args.steps, save_at=(args.save_at,),
            kill_save=args.kill_at)
        M.destroy_model_parallel()
    except SimulatedPreemption:
        # the SIGKILL stand-in: die HARD, no cleanup, no result file —
        # exactly what a preempted host leaves behind
        os._exit(KILLED_RC)
    result.update(
        losses=losses, steady_recompiles=retraces,
        refusal=refusal,
        last_committed=seg["manager"].last_committed_step,
        stats=seg["manager"].stats())
    np.save(os.path.join(args.result_dir, f"canonical{pid}.npy"),
            _canonical(seg))
    with open(os.path.join(args.result_dir, f"proc{pid}.json"),
              "w") as f:
        json.dump(result, f, sort_keys=True)
    return 0


# ---------------------------------------------------------------------------
# fleet driver
# ---------------------------------------------------------------------------

def _launch_fleet(ckpt_dir, result_dir, *, steps, save_at, kill_at=None,
                  chaos_env=None, port=12411, timeout=300.0):
    """One 2-host × 4-device fleet through parallel/multiproc.  Chaos
    env vars are injected for the children and scrubbed after."""
    from apex_tpu.parallel import multiproc

    os.makedirs(result_dir, exist_ok=True)
    saved = {}
    for k, v in (chaos_env or {}).items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    try:
        argv = ["--nproc", "2", "--devices-per-proc", "4",
                "--coordinator", f"127.0.0.1:{port}",
                "--timeout", str(timeout), "--grace", "120",
                os.path.abspath(__file__), "--worker",
                "--ckpt-dir", ckpt_dir, "--result-dir", result_dir,
                "--steps", str(steps), "--save-at", str(save_at),
                "--dp", "4", "--barrier-timeout", "6"]
        if kill_at is not None:
            argv += ["--kill-at", str(kill_at)]
        return multiproc.main(argv)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _read_results(result_dir):
    import numpy as np
    out = {}
    for p in (0, 1):
        j = os.path.join(result_dir, f"proc{p}.json")
        if os.path.exists(j):
            with open(j) as f:
                out[p] = json.load(f)
            c = os.path.join(result_dir, f"canonical{p}.npy")
            if os.path.exists(c):
                out[p]["canonical"] = np.load(c)
    return out


def probe(steps: int, save_at: int, as_json: bool, smoke: bool) -> int:
    import shutil
    import tempfile

    import numpy as np

    from apex_tpu.checkpoint import (
        ElasticOrchestrator, EscalationError, IncompleteCheckpointError,
        chaos, latest_committed_step, load_model_state, verify_shards)
    from apex_tpu.checkpoint import sharded as S
    from apex_tpu.checkpoint.chaos import LostRankWatchdog
    from apex_tpu.monitor.trace.straggler import StragglerDetector
    from apex_tpu.parallel import mesh as M

    root = tempfile.mkdtemp(prefix="fleet_probe_")
    result = {"steps": steps, "save_at": save_at, "smoke": smoke,
              "dp_fleet": 4, "n_hosts": 2}
    failures = []
    port = [12431]

    def fleet(tag, **kw):
        port[0] += 1
        d = os.path.join(root, tag, "ckpt")
        r = os.path.join(root, tag, "results")
        os.makedirs(d, exist_ok=True)
        rc = _launch_fleet(d, r, steps=steps, save_at=save_at,
                           port=port[0], **kw)
        return d, _read_results(r), rc

    try:
        # 1. BASELINE fleet: both hosts finish, commit at save_at,
        # agree bitwise (the free cross-host consistency check)
        base_dir, base, rc = fleet("baseline")
        if rc != 0:
            failures.append(f"baseline fleet exited {rc}")
        if sorted(base) != [0, 1]:
            failures.append(f"baseline: missing host results "
                            f"{sorted(base)}")
        else:
            if base[0]["losses"] != base[1]["losses"] or not \
                    np.array_equal(base[0]["canonical"],
                                   base[1]["canonical"]):
                failures.append(
                    "baseline: the two hosts' trajectories are NOT "
                    "bitwise identical — deterministic replication "
                    "broke, every downstream claim is void")
            for p, r in base.items():
                if r["steady_recompiles"]:
                    failures.append(f"baseline host {p}: "
                                    f"{r['steady_recompiles']} steady "
                                    "recompiles")
        lc = latest_committed_step(base_dir)
        result["baseline_committed"] = lc
        if lc != save_at:
            failures.append(f"baseline: latest committed {lc}, "
                            f"expected {save_at}")
        barrier = base.get(0, {}).get("stats", {}).get(
            "ckpt_commit_barrier_s")
        result["ckpt_commit_barrier_s"] = barrier
        if barrier is None:
            failures.append("baseline: process 0 never stamped "
                            "ckpt_commit_barrier_s")
        ms = load_model_state(base_dir, save_at)
        if "rng_key" not in ms:
            failures.append("baseline: model state (rng_key) missing "
                            "from the committed manifest")

        # 2. KILL MATRIX: one fleet per fail point; killing any one
        # host leaves save_at committed + loadable and never a torn
        # newer commit.  Process-0 survivors must REFUSE by name.
        matrix = [
            # (tag, chaos env, which host dies, survivor-refuses?)
            ("kill_submanifest",
             {"APEX_TPU_CHAOS_SAVE": "host.before_submanifest",
              "APEX_TPU_CHAOS_PROC": "1"}, 1, True),
        ] if smoke else [
            ("kill_shard_write",
             {"APEX_TPU_CHAOS_SAVE": "ckpt.mid_shards:2",
              "APEX_TPU_CHAOS_PROC": "1"}, 1, True),
            ("kill_submanifest",
             {"APEX_TPU_CHAOS_SAVE": "host.before_submanifest",
              "APEX_TPU_CHAOS_PROC": "1"}, 1, True),
            ("kill_before_barrier",
             {"APEX_TPU_CHAOS_SAVE": "host.before_barrier",
              "APEX_TPU_CHAOS_PROC": "0"}, 0, False),
            # host 1 dies mid-STEP (not mid-save): the surviving
            # process 0 reaches the kill-step save alone and its
            # barrier must refuse the half-fleet commit
            ("kill_rank_lost",
             {"APEX_TPU_CHAOS": f"rank.lost_at_step:{save_at + 2}",
              "APEX_TPU_CHAOS_PROC": "1"}, 1, True),
        ]
        for tag, env, dead, expect_refusal in matrix:
            d, res, rc = fleet(tag, kill_at=steps, chaos_env=env)
            lc = latest_committed_step(d)
            result[f"{tag}_committed"] = lc
            if lc != save_at:
                failures.append(
                    f"{tag}: latest committed is {lc}, expected "
                    f"{save_at} — a torn commit became visible")
            else:
                try:
                    verify_shards(S.step_dir(d, save_at))
                except Exception as e:
                    failures.append(f"{tag}: committed step no longer "
                                    f"loads: {e}")
            if dead in res:
                failures.append(f"{tag}: host {dead} wrote a result "
                                "after being killed?")
            survivor = 1 - dead
            if survivor not in res:
                failures.append(f"{tag}: surviving host {survivor} "
                                "never finished (hung on the dead "
                                "sibling?)")
            elif expect_refusal and survivor == 0:
                refusal = res[0].get("refusal")
                if not refusal or f"host {dead}" not in refusal:
                    failures.append(
                        f"{tag}: process 0 survived but did not refuse "
                        f"the torn commit naming host {dead} "
                        f"(refusal={refusal!r})")
            result[f"{tag}_ok"] = not any(
                f.startswith(tag) for f in failures)

        # 3. ORCHESTRATOR RESUME off the baseline commit.
        base_losses = base.get(0, {}).get("losses")
        base_canon = base.get(0, {}).get("canonical")
        if base_canon is None or base_losses is None:
            # the baseline failure above is the real story — don't let
            # a None-armed np.allclose bury it under a TypeError
            failures.append(
                "orchestrator sections skipped: no baseline host-0 "
                "result to compare against")
            raise _SkipToReport()

        def build(dp, resume_step, attempt):
            seg = _build_segment(dp, base_dir, resume_step=resume_step,
                                 manager_kw=dict(attempt=attempt))

            def session(on_step=None):
                losses, retraces, _ = _drive(
                    seg, _make_batches(steps, seg["batch"],
                                       seg["cfg"].seq_len,
                                       seg["cfg"].vocab_size),
                    resume_step or 0, steps, on_step=on_step)
                M.destroy_model_parallel()
                return {"losses": losses, "retraces": retraces,
                        "canonical": _canonical(seg)}
            return session

        # 3a. equal topology: bitwise
        out = ElasticOrchestrator(base_dir, build, initial_dp=4).run()
        eq = (base_losses is not None
              and out["losses"] == base_losses[save_at:]
              and np.array_equal(out["canonical"], base_canon))
        result["equal_topology_bitwise"] = bool(eq)
        if not eq:
            failures.append("equal-topology orchestrator resume NOT "
                            "bitwise vs the fleet baseline")
        if out["retraces"]:
            failures.append(f"equal-topology resume: {out['retraces']} "
                            "steady recompiles")

        # 3b. lost rank mid-segment → dump → rebuild dp=4→2 →
        # re-shard restore → resume (allclose, resume_probe's
        # calibrated tolerances)
        det = StragglerDetector(threshold=1.5, patience=2)
        wd = LostRankWatchdog(det, deadline=2)
        dump_path = os.path.join(root, "fleet_flight.json")
        from apex_tpu.monitor import FlightRecorder
        recorder = FlightRecorder(dump_path, capacity=4)

        def build_elastic(dp, resume_step, attempt):
            session = build(dp, resume_step, attempt)

            def on_step(i):
                if dp == 4 and i >= save_at + 1:
                    # rank 2 goes 3x median: flagged, then lost
                    t = np.full((dp, 1), 0.1)
                    t[2, 0] = 0.3
                    wd.check(t)

            return lambda: session(on_step=on_step)

        orch = ElasticOrchestrator(
            base_dir, build_elastic, initial_dp=4,
            choose_dp=lambda dp, e: 2, recorder=recorder, watchdog=wd)
        out2 = orch.run()
        close = bool(np.allclose(base_canon, out2["canonical"],
                                 rtol=1e-3, atol=5e-4))
        result["elastic_allclose"] = close
        result["elastic_max_abs_diff"] = float(
            np.abs(base_canon - out2["canonical"]).max())
        result["fleet_resumes"] = orch.stats()["fleet_resumes"]
        result["fleet_dp"] = orch.stats()["fleet_dp"]
        if not close:
            failures.append(
                f"elastic dp=4→2 resume diverged (max abs diff "
                f"{result['elastic_max_abs_diff']:.3e})")
        if out2["retraces"]:
            failures.append(f"elastic resume: {out2['retraces']} "
                            "steady recompiles")
        if orch.stats() != {"fleet_resumes": 1, "fleet_dp": 2}:
            failures.append(f"orchestrator stats {orch.stats()} != "
                            "one resume at dp=2")
        if not os.path.exists(dump_path):
            failures.append("lost-rank recovery never dumped a flight "
                            "report")
        else:
            with open(dump_path) as f:
                reason = json.load(f).get("reason", "")
            if f"last committed checkpoint: step {save_at}" not in reason:
                failures.append(
                    "flight dump reason does not name the resume "
                    f"point: {reason!r}")

        # 4a. negative control, asserted BY NAME: damage the committed
        # step and the completeness sweep must refuse naming the rank
        chaos.truncate_shard(S.step_dir(base_dir, save_at),
                             "params_shard", rank=3)
        try:
            verify_shards(S.step_dir(base_dir, save_at))
            failures.append("negative control: truncated shard was "
                            "NOT refused")
        except IncompleteCheckpointError as e:
            if "rank 3" not in str(e):
                failures.append("negative control: refusal lost its "
                                f"rank naming: {e}")
        result["negative_control_ok"] = not any(
            "negative control" in f for f in failures)

        # 4b. hard escalation: no committed checkpoint → EscalationError
        empty = os.path.join(root, "empty_ckpt")
        os.makedirs(empty, exist_ok=True)

        def build_doomed(dp, resume_step, attempt):
            def session():
                from apex_tpu.checkpoint.chaos import RankLostError
                raise RankLostError("rank 1 lost (seeded)", rank=1)
            return session

        try:
            ElasticOrchestrator(empty, build_doomed, initial_dp=2).run()
            failures.append("escalation: orchestrator resumed with NO "
                            "committed checkpoint")
        except EscalationError as e:
            if "NO committed checkpoint" not in str(e):
                failures.append(f"escalation lost its naming: {e}")
        result["escalation_ok"] = not any(
            "escalation" in f for f in failures)
    except _SkipToReport:
        pass
    finally:
        shutil.rmtree(root, ignore_errors=True)

    result["ok"] = not failures
    if as_json:
        # ONE line so callers can reverse-scan stdout past log noise
        print(json.dumps(result, sort_keys=True))
    else:
        for k in sorted(result):
            print(f"  {k}: {result[k]}")
    if failures:
        for f in failures:
            print(f"fleet_probe: FAIL — {f}", file=sys.stderr)
        return 1
    print("fleet_probe: OK (kill matrix green, multi-host commit "
          "barrier held, orchestrator resumed bitwise/allclose, zero "
          "steady recompiles after resume)")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description="multi-host commit kill matrix + elastic-resume "
                    "orchestration CI gate")
    ap.add_argument("--selftest", action="store_true",
                    help="fixture drift gate; exit 1 on drift")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 subset: one kill point + resume")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--save-at", type=int, default=4,
                    help="commit a checkpoint after this step")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable result")
    ap.add_argument("--backend", default=None,
                    help="JAX_PLATFORMS override (resolved pre-import)")
    # worker mode (internal; spawned via parallel/multiproc)
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--result-dir", default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--dp", type=int, default=4, help=argparse.SUPPRESS)
    ap.add_argument("--kill-at", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--attempt", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--barrier-timeout", type=float, default=6.0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.selftest:
        return selftest()
    if args.worker:
        if not (args.ckpt_dir and args.result_dir):
            ap.error("--worker needs --ckpt-dir and --result-dir")
        return worker(args)
    if not 0 < args.save_at < args.steps:
        ap.error(f"--save-at must be in (0, {args.steps})")
    return probe(args.steps, args.save_at, args.json, args.smoke)


if __name__ == "__main__":
    sys.exit(main())
