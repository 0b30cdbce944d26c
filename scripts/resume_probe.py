"""Preemption/resume CI gate (ISSUE 9): save → kill → restore →
trajectory-match.

usage:
  python scripts/resume_probe.py             # full probe
  python scripts/resume_probe.py --selftest  # fixture drift gate
  python scripts/resume_probe.py --json      # machine-readable result

The full probe drives the whole preemption story on a real train step
(ZeRO-2 `DistributedFusedAdam` through `ddp.make_train_step`, amp
dynamic loss scaling, `CheckpointManager` async saves):

  1. BASELINE   — dp=2 trains `--steps` steps over fixed data, with a
                  committed checkpoint at `--save-at`.
  2. KILL       — a `chaos` fail point kills a later save mid-write;
                  the probe asserts the partial directory is NOT
                  loadable and the `--save-at` commit still restores
                  (the latest COMMITTED manifest always restores).
  3. RESUME =   — a fresh dp=2 run restores at `--save-at` and replays
                  the remaining steps: losses and the canonical master
                  flat must match the unpreempted baseline BITWISE.
  4. RESUME ≠   — dp=1 and dp=4 runs restore the SAME dp=2 checkpoint
                  (elastic re-shard + full gather): canonical master
                  flats must match allclose (fp reduction order is the
                  only difference — docs/checkpointing.md's matrix).
  5. SENTRY     — every resumed run is RecompileSentry-wrapped and
                  must show ZERO steady-state recompiles after the
                  resume warmup (restored state places through the
                  step's own partition specs, so nothing retraces).

Exit is nonzero on any mismatch.  On a CPU backend an 8-way virtual
device mesh is forced (conftest-style) and the tiny smoke config
substitutes through the same build path; on TPU run it as-is on a
multi-chip slice.

`--selftest` is the tier-1 fixture-drift gate (mirrors
`lint_step.py` / `comms_probe.py` / `flight_report.py`): the committed
manifest fixture (scripts/resume_fixture.json) must still validate,
the reshard round-trip must reproduce a synthetic canonical buffer
bitwise, and a seeded truncated shard must be REFUSED with the missing
rank named (the gate's own negative control).
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
# elastic resume needs dp up to 4: on the CPU backend force an 8-way
# virtual mesh (must precede the first jax import, conftest-style)
if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "resume_fixture.json")


# ---------------------------------------------------------------------------
# selftest (tier-1)
# ---------------------------------------------------------------------------

def selftest() -> int:
    import numpy as np

    from apex_tpu.checkpoint import (IncompleteCheckpointError, chaos,
                                     save_sharded, validate_manifest,
                                     verify_shards)
    from apex_tpu.checkpoint import sharded as S

    with open(FIXTURE) as f:
        fixture = json.load(f)
    try:
        validate_manifest(fixture)
    except S.CheckpointError as e:
        print(f"resume_probe --selftest: SCHEMA DRIFT — {e}",
              file=sys.stderr)
        print("(bump-side change? regenerate scripts/"
              "resume_fixture.json with the new manifest schema)",
              file=sys.stderr)
        return 1

    # reshard round-trip: a synthetic 2-bucket dp=2 layout re-laid to
    # dp=4 single-bucket and back must reproduce the canonical buffer
    # bitwise (the elastic-resume math, no devices involved)
    src = {"align": 1, "total": 16, "n_tensors": 3, "num_shards": 2,
           "n_buckets": 2, "bucket_totals": [10, 6],
           "bucket_padded": [12, 8], "master_dtype": "float32"}
    dst = {"align": 1, "total": 16, "n_tensors": 3, "num_shards": 4,
           "n_buckets": 1, "bucket_totals": [16],
           "bucket_padded": [32], "master_dtype": "float32"}
    canon = np.arange(16, dtype=np.float32)
    shards = list(np.split(S.relayout_flat(canon, src), 2))
    re4 = S.reshard(shards, src, dst)
    back = S.canonical_flat(list(np.split(re4, 4)), dst)
    if not np.array_equal(back, canon):
        print("resume_probe --selftest: reshard round-trip is no longer "
              f"bitwise ({back} != {canon})", file=sys.stderr)
        return 1

    # negative control: a committed-then-truncated shard must be
    # REFUSED with the damaged rank named — a gate that stops flagging
    # its seeded corruption is not a gate
    import shutil
    import tempfile
    tmp = tempfile.mkdtemp(prefix="resume_probe_selftest_")
    try:
        p = save_sharded(
            tmp, 3,
            {"params_shard": ("sharded",
                              list(np.split(np.arange(8, dtype=np.float32),
                                            2))),
             "step": ("replicated", np.asarray(3, np.int32))},
            flat_layout={"align": 1, "total": 8, "n_tensors": 1,
                         "num_shards": 2, "n_buckets": 1,
                         "bucket_totals": [8], "bucket_padded": [8],
                         "master_dtype": "float32"})
        verify_shards(p)
        chaos.truncate_shard(p, "params_shard", rank=1)
        try:
            verify_shards(p)
        except IncompleteCheckpointError as e:
            if "rank 1" not in str(e) or "truncated" not in str(e):
                print("resume_probe --selftest: truncation error lost "
                      f"its rank/cause naming: {e}", file=sys.stderr)
                return 1
        else:
            print("resume_probe --selftest: seeded TRUNCATED shard was "
                  "NOT refused — verify_shards lost its teeth",
                  file=sys.stderr)
            return 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print("resume_probe --selftest: OK")
    return 0


# ---------------------------------------------------------------------------
# full probe
# ---------------------------------------------------------------------------

def _make_batches(n_steps, batch, seq, vocab):
    import numpy as np
    rng = np.random.RandomState(1234)
    out = []
    for _ in range(n_steps):
        t = rng.randint(0, vocab, size=(batch, seq)).astype(np.int32)
        out.append((t, np.roll(t, -1, axis=1)))
    return out


def _run_segment(dp, ckpt_dir, batches, start, stop, *, cfg, batch_spec,
                 save_at=None, resume=False, n_buckets=2):
    """Build a fresh dp-way ZeRO-2 train step (optionally restoring
    `ckpt_dir`'s latest commit first), run steps [start, stop), saving
    on `save_at`.  Returns (losses, canonical_master, steady_recompiles,
    scale)."""
    import jax
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.checkpoint import CheckpointManager
    from apex_tpu.checkpoint import sharded as S
    from apex_tpu.monitor.compile import RecompileSentry
    from apex_tpu.optimizers.distributed_fused_adam import (
        DistributedFusedAdam,
    )
    from apex_tpu.parallel import ddp
    from apex_tpu.parallel import mesh as M
    from apex_tpu.models.gpt import GPT
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:dp])
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    amp_state = amp.initialize(opt_level="O0", loss_scale="dynamic")
    scaler = amp_state.loss_scalers[0]
    opt = DistributedFusedAdam(num_shards=dp, lr=1e-2,
                               n_buckets=n_buckets, use_pallas=False)
    sspec = opt.state_partition_specs()
    state = jax.jit(shard_map(opt.init, mesh=mesh, in_specs=(P(),),
                              out_specs=sspec, check_vma=False))(params)
    manager = CheckpointManager(ckpt_dir, opt, every_n_steps=1,
                                keep=4)
    if resume:
        state, restored_scaler, _ = manager.restore(mesh)
        if restored_scaler is not None:
            scaler = restored_scaler
    step = ddp.make_train_step(
        lambda p, b: model.loss(p, b[0], b[1]), opt, mesh,
        amp_state=amp_state, batch_spec=batch_spec)
    sentry = RecompileSentry(step, name=f"resume_probe_dp{dp}",
                             warn=False)
    losses = []
    calls = 0
    for i in range(start, stop):
        t, l = batches[i]
        state, scaler, loss = sentry(state, scaler, (t, l))
        calls += 1
        if calls == 2:
            # the resume contract: first call compiles, a donated-state
            # second compile is legitimate — anything after is a
            # steady-state retrace and fails the probe
            _ = np.asarray(loss)
            sentry.mark_steady()
        losses.append(np.asarray(loss, np.float32))
        if save_at is not None and (i + 1) == save_at:
            manager.save(save_at, state, scaler)
            manager.wait()
    if calls == 1:
        sentry.mark_steady()
    glob = np.asarray(state.params_shard)
    canonical = S.canonical_flat(list(np.split(glob, dp)),
                                 opt.shard_layout())
    scale = float(np.asarray(scaler.scale))
    manager.wait()
    M.destroy_model_parallel()
    return (np.asarray(losses, np.float32), canonical,
            int(sentry.steady_recompiles), scale)


def probe(steps: int, save_at: int, as_json: bool) -> int:
    import shutil
    import tempfile

    import jax
    import numpy as np

    from apex_tpu.checkpoint import (chaos, latest_committed_step)
    from apex_tpu.checkpoint.chaos import SimulatedPreemption
    from apex_tpu.models.gpt import GPTConfig
    from jax.sharding import PartitionSpec as P

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    n_dev = len(jax.devices())
    if n_dev < 2:
        print("resume_probe: needs >= 2 devices for the dp=2 baseline",
              file=sys.stderr)
        return 2
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, seq_len=512, hidden=512,
                        num_layers=4, num_heads=8, dropout=0.0)
        batch = 8
    else:
        cfg = GPTConfig(vocab_size=64, seq_len=16, hidden=32,
                        num_layers=2, num_heads=2, dropout=0.0)
        batch = 8
    batches = _make_batches(steps, batch, cfg.seq_len, cfg.vocab_size)
    batch_spec = (P("dp"), P("dp"))
    tmp = tempfile.mkdtemp(prefix="resume_probe_")
    result = {"steps": steps, "save_at": save_at, "dp_baseline": 2}
    failures = []
    try:
        # 1. baseline (unpreempted) with a commit at save_at
        losses, canon, retraces, _ = _run_segment(
            2, tmp, batches, 0, steps, cfg=cfg, batch_spec=batch_spec,
            save_at=save_at)
        result["baseline_loss_first"] = float(losses[0])
        result["baseline_loss_last"] = float(losses[-1])
        if retraces:
            failures.append(f"baseline: {retraces} steady recompiles")

        # 2. kill-mid-save: a later save dies after its first shard
        # file; the partial must not be loadable and save_at must
        # still restore
        with chaos.preempt_at("ckpt.mid_shards", count=2):
            try:
                losses2, _, _, _ = _run_segment(
                    2, tmp, batches, 0, steps, cfg=cfg,
                    batch_spec=batch_spec, save_at=steps)
                failures.append("kill-mid-save: fail point never fired")
            except SimulatedPreemption:
                pass
        last = latest_committed_step(tmp)
        result["last_committed_after_kill"] = last
        if last != save_at:
            failures.append(
                f"kill-mid-save: latest committed step is {last}, "
                f"expected {save_at} (partial directory counted as a "
                "checkpoint?)")

        # 3. equal-topology resume: bitwise
        r_losses, r_canon, r_retraces, _ = _run_segment(
            2, tmp, batches, save_at, steps, cfg=cfg,
            batch_spec=batch_spec, resume=True)
        eq_losses = bool(np.array_equal(losses[save_at:], r_losses))
        eq_canon = bool(np.array_equal(canon, r_canon))
        result["equal_topology_bitwise"] = eq_losses and eq_canon
        if not eq_losses:
            failures.append(
                "equal-topology resume: loss trajectory NOT bitwise "
                f"({losses[save_at:]} vs {r_losses})")
        if not eq_canon:
            failures.append(
                "equal-topology resume: canonical master flat NOT "
                "bitwise")
        if r_retraces:
            failures.append(
                f"equal-topology resume: {r_retraces} steady-state "
                "recompile(s) after resume")

        # 4. elastic resume: dp=2 checkpoint → dp=1 (full gather) and
        # dp=4 (re-shard); fp reduction order differs, so allclose
        for dp in (1, 4):
            if dp > n_dev:
                result[f"dp{dp}_skipped"] = f"only {n_dev} devices"
                continue
            e_losses, e_canon, e_retraces, _ = _run_segment(
                dp, tmp, batches, save_at, steps, cfg=cfg,
                batch_spec=batch_spec, resume=True)
            # tolerance calibration: two FROM-SCRATCH runs at dp=1 vs
            # dp=2 on this config already differ by ~5e-5 max-abs after
            # 8 steps (grad psum_scatter reduction order through Adam's
            # normalized early updates) — the resume moves values
            # bitwise, so the only legitimate divergence is that same
            # class.  10x margin over it still catches real corruption,
            # which is O(param magnitude), 3+ orders larger.
            close = bool(np.allclose(canon, e_canon, rtol=1e-3,
                                     atol=5e-4))
            result[f"dp{dp}_allclose"] = close
            result[f"dp{dp}_max_abs_diff"] = float(
                np.abs(canon - e_canon).max())
            if not close:
                failures.append(
                    f"dp=2→dp={dp} resume: canonical master flat "
                    f"diverged (max abs diff "
                    f"{result[f'dp{dp}_max_abs_diff']:.3e})")
            if e_retraces:
                failures.append(
                    f"dp=2→dp={dp} resume: {e_retraces} steady-state "
                    "recompile(s) after resume")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    result["ok"] = not failures
    if as_json:
        # ONE line so callers can reverse-scan stdout past log noise
        print(json.dumps(result, sort_keys=True))
    else:
        for k in sorted(result):
            print(f"  {k}: {result[k]}")
    if failures:
        for f in failures:
            print(f"resume_probe: FAIL — {f}", file=sys.stderr)
        return 1
    print("resume_probe: OK (kill-mid-save survived, equal-topology "
          "resume bitwise, elastic resume allclose, zero steady-state "
          "recompiles after resume)")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description="save→kill→restore→trajectory-match CI gate")
    ap.add_argument("--selftest", action="store_true",
                    help="fixture drift gate; exit 1 on drift")
    ap.add_argument("--steps", type=int, default=8,
                    help="total training steps (default 8)")
    ap.add_argument("--save-at", type=int, default=4,
                    help="commit a checkpoint after this step")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable result")
    ap.add_argument("--backend", default=None,
                    help="JAX_PLATFORMS override (resolved pre-import)")
    args = ap.parse_args()
    if args.selftest:
        return selftest()
    if not 0 < args.save_at < args.steps:
        ap.error(f"--save-at must be in (0, {args.steps})")
    return probe(args.steps, args.save_at, args.json)


if __name__ == "__main__":
    sys.exit(main())
