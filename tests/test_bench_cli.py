"""The bench.py CLI contract, pinned without touching a device: the
`--only` one-process path, its refusal to print a CPU number under a
device metric's name, and that the benchmark starts no child process
(a chip belongs to one process at a time)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench


def test_only_registry_rounds(monkeypatch):
    """_ONLY calls the measurement once, in this process, and rounds
    to 0.1."""
    calls = []

    def fake_resnet(on_tpu):
        calls.append(on_tpu)
        return 2345.6789

    monkeypatch.setattr(bench, "_resnet50_img_per_sec", fake_resnet)
    assert bench._ONLY["resnet50_img_per_sec"](True) == 2345.7
    assert calls == [True]


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_chip_entry_scripts_start_no_child_process(script):
    """One process per chip: a parent that has touched JAX holds the
    chip, and a child that needs it then fails or hangs — so the
    scripts that run on the chip never spawn one."""
    src = open(os.path.join(ROOT, script)).read()
    assert "subprocess" not in src and "os.system" not in src
    assert "multiprocessing" not in src


def test_only_wrong_arity_exits_with_usage(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["bench.py", "--only"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 2
    assert "usage" in capsys.readouterr().err


def test_only_unknown_metric_lists_choices(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["bench.py", "--only", "nope"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "unknown metric nope" in err
    assert "resnet50_img_per_sec" in err


def test_only_valid_metric_on_cpu_backend_exits_3(monkeypatch, capsys):
    """--only on a CPU backend exits 3 and prints no metric: a CPU
    number never appears under a device metric's name."""
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--only", "resnet50_img_per_sec"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 3


# --------------------------- bench_diff.py ---------------------------

def _bench_diff():
    """Import scripts/bench_diff.py as a module (the scripts dir is
    not a package — load by path, the engine is pure)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "bench_diff.py")
    spec = importlib.util.spec_from_file_location("bench_diff", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_diff_direction_table():
    """Direction-aware verdicts (ISSUE 15 satellite): tokens/s down =
    regress, p99 up = regress, busy fraction up = improve — and a
    metric with no known polarity gets NO verdict, never a guess."""
    bd = _bench_diff()
    assert bd.metric_direction("gpt1p3b_tokens_per_sec_per_chip") == 1
    assert bd.metric_direction("value") == 1
    assert bd.metric_direction("serve_goodput_tokens_per_sec") == 1
    assert bd.metric_direction("serve_p99_ms") == -1
    assert bd.metric_direction("adam_1b_step_ms") == -1
    assert bd.metric_direction("ckpt_blocking_s") == -1
    assert bd.metric_direction("timeline_host_gap_ms") == -1
    assert bd.metric_direction("timeline_device_busy_fraction") == 1
    assert bd.metric_direction("moe_drop_fraction") == -1
    assert bd.metric_direction("comms_comm_fraction") == -1
    assert bd.metric_direction("baseline_batch") == 0
    assert bd.metric_direction("serve_pool_util_peak") == 0


def test_bench_diff_engine_thresholds_and_bools():
    bd = _bench_diff()
    old = {"value": 100.0, "serve_p99_ms": 10.0, "lint_ok": True,
           "mystery_number": 5.0, "gone_metric": 1.0}
    new = {"value": 90.0, "serve_p99_ms": 10.4, "lint_ok": False,
           "mystery_number": 50.0, "new_metric": 2.0}
    res = bd.diff_metrics(old, new, threshold_pct=5.0)
    by = {r["metric"]: r for r in res["rows"]}
    assert by["value"]["verdict"] == "REGRESS"          # -10% tokens/s
    assert by["serve_p99_ms"]["verdict"] == "ok"        # +4% < 5%
    assert by["lint_ok"]["verdict"] == "REGRESS"        # True -> False
    assert by["mystery_number"]["verdict"] == "n/a"     # no polarity
    assert res["only_in_new"] == ["new_metric"]
    assert res["only_in_old"] == ["gone_metric"]
    assert set(res["regressions"]) == {"value", "lint_ok"}
    assert not res["ok"]
    # a verdict FLAG vanishing must be listed, never silently dropped
    # (review fix): bool on one side only lands in only_in_*
    res_b = bd.diff_metrics({"comms_overlap_ok": True, "value": 1.0},
                            {"value": 1.0, "new_flag": False},
                            threshold_pct=5.0)
    assert res_b["only_in_old"] == ["comms_overlap_ok"]
    assert res_b["only_in_new"] == ["new_flag"]
    # a wider threshold absorbs the drop
    res2 = bd.diff_metrics(old, new, threshold_pct=15.0)
    assert "value" not in res2["regressions"]


def test_bench_diff_cli_selftest_and_exit_codes(tmp_path):
    """The committed mini-fixtures drive --selftest (drift gate), and
    the CLI exits nonzero exactly when a regression survived the
    threshold."""
    import json
    import subprocess

    root = ROOT
    script = os.path.join(root, "scripts", "bench_diff.py")
    r = subprocess.run([sys.executable, script, "--selftest"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "bench_diff --selftest: OK" in r.stdout
    # fixture A -> B: the seeded regressions exit 1 and are named
    fa = os.path.join(root, "scripts", "bench_diff_fixture_a.json")
    fb = os.path.join(root, "scripts", "bench_diff_fixture_b.json")
    r2 = subprocess.run([sys.executable, script, fa, fb],
                        capture_output=True, text=True, timeout=120)
    assert r2.returncode == 1
    assert "REGRESS" in r2.stdout and "serve_p99_ms" in r2.stdout
    # identical files diff clean, exit 0 — and the BENCH_r* driver
    # wrapper ("parsed") unwraps
    wrapped = tmp_path / "w.json"
    wrapped.write_text(json.dumps(
        {"n": 1, "rc": 0, "parsed": json.load(open(fa))}))
    r3 = subprocess.run([sys.executable, script, str(wrapped), fa],
                        capture_output=True, text=True, timeout=120)
    assert r3.returncode == 0, r3.stdout + r3.stderr
    assert "no regression" in r3.stdout


def test_timed_records_duration_even_on_error():
    """Per-metric wall clock (ISSUE 2 satellite): _timed stamps the
    durations dict on success AND on the error path (a 15-min OOM
    spiral must be visible in the BENCH trajectory), and the JSON gains
    monitor_schema_version for cross-round comparability."""
    durations = {}
    with bench._timed(durations, "ok"):
        pass
    with pytest.raises(RuntimeError):
        with bench._timed(durations, "boom"):
            raise RuntimeError("x")
    assert set(durations) == {"ok", "boom"}
    assert all(isinstance(v, float) and v >= 0 for v in durations.values())

    from apex_tpu import monitor
    assert isinstance(monitor.SCHEMA_VERSION, int)
