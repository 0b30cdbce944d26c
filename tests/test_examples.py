"""Smoke tests for the example scripts (≡ the reference's examples/
being exercised by tests/L1 shell drivers, tests/L1/common/run_test.sh).

Each example must run end-to-end on the CPU test mesh and report a
finite loss — the L1 tier's "does the intended workflow actually run"
check, scaled down to CI size.
"""

import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run(script, *args, timeout=600):
    return subprocess.run(
        [sys.executable, str(ROOT / "examples" / script), *args],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.mark.parametrize("opt_level", ["O1", "O2"])
def test_dcgan_runs(opt_level):
    r = _run("dcgan_amp.py", "--batch-size", "8", "--image-size", "32",
             "--iters", "6", "--opt-level", opt_level,
             "--force-cpu-devices", "1")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Loss_D" in r.stdout and "nan" not in r.stdout.lower()


def test_simple_distributed_runs():
    r = _run("simple_distributed.py")
    assert r.returncode == 0, r.stderr[-2000:]


def test_long_context_training_runs():
    """Ring-attention (zigzag) context-parallel LM training end to end
    on the 8-way mesh — the long-context recipe the reference cannot
    express (FMHA seq cap 512)."""
    r = _run("long_context_training.py", "--seq", "8192", "--steps", "2",
             "--force-cpu-devices", "8")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("step")]
    assert len(lines) == 2, r.stdout
    losses = [float(ln.split("loss")[1].split()[0]) for ln in lines]
    assert all(l == l and abs(l) < 1e9 for l in losses), losses
    assert losses[1] < losses[0], losses


def test_train_with_monitor_runs(tmp_path):
    """ISSUE 2 tier-1 gate: the telemetry demo trains 3 steps on CPU
    and every metrics JSONL line validates against the monitor schema
    (required fields, finite values, monotonic steps)."""
    import json

    from apex_tpu import monitor

    jsonl = tmp_path / "metrics.jsonl"
    r = _run("train_with_monitor.py", "--steps", "3",
             "--jsonl", str(jsonl), "--force-cpu-devices", "1")
    assert r.returncode == 0, r.stderr[-2000:]
    records = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    # the stream interleaves full step records with ScalarWriter timer
    # tags; the schema governs the step records
    step_records = [rec for rec in records if "loss" in rec]
    assert len(step_records) == 3, records
    monitor.validate_records(step_records)  # raises on NaN/non-monotonic
    for rec in step_records:
        assert rec["tokens_per_sec"] > 0
        assert rec["step_time_ms"] > 0
    assert any("train-step-time" in rec for rec in records), \
        "Timers.write scalars missing from the JSONL stream"


def test_serve_gpt_runs_64_streams():
    """ISSUE 8 acceptance: the continuous-batching demo decodes N=64
    concurrent ragged streams on the CPU smoke config with ZERO
    steady-state recompiles — the script itself exits nonzero if the
    sentry tripped or any request failed to retire."""
    r = _run("serve_gpt.py", "--streams", "64",
             "--force-cpu-devices", "1")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "serve_gpt: OK (zero steady-state recompiles)" in r.stdout
    assert "decoded 64 requests" in r.stdout


def test_serve_gpt_drain_path_64_streams():
    """ISSUE 14 satellite: the graceful-drain path (the SIGTERM
    handler's exact code, driven deterministically) at N=64 CPU —
    every live request finishes, the queued remainder rides the
    restorable snapshot, and the script exits nonzero if any live
    request is lost."""
    r = _run("serve_gpt.py", "--streams", "64", "--max-new", "8",
             "--drain-after-steps", "6", "--force-cpu-devices", "1")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "serve_gpt: drain OK (no live request lost)" in r.stdout
    assert "restorable snapshot" in r.stdout
