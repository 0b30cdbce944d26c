"""The runner end to end on the CPU, through `run.py --rehearse`: a tiny
cell of each job shape, the shape of the last line, a negative control
for `correct`, and that a cell, a configuration, a reference and a
per-layer metric are added as files alone.

Run by hand: `python -m pytest benchmarks/tests -q` (about two minutes).
No number printed here is a measurement: a rehearsal reports null for
every metric that only the chip can give.
"""

import copy
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
REHEARSAL = os.path.join(HERE, "data", "rehearsal")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(manifest, workload, trace=0, seed=5, rehearse=True, seconds=1.5):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
           manifest, "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd + (["--rehearse"] if rehearse else []),
                          capture_output=True, text=True, timeout=600,
                          env=env, cwd=REPO)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def load(path):
    with open(path) as f:
        return json.load(f)


def dump(obj, *path):
    path = os.path.join(*path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)
    return path


@pytest.fixture
def tmp_bench(tmp_path):
    """A benchmark of one tiny cell in a directory of its own: a
    manifest, a configuration and a workload file, and nothing else —
    the job, the reference and the readers are found beside run.py."""
    manifest = load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    manifest["paths"] = ["bm"]
    manifest["configs"] = [dict(manifest["configs"][0], name="wee-gpt",
                                file="bm/configs/wee-gpt.json")]
    manifest["workloads"] = [{"name": "wee-gpt.train", "config": "wee-gpt",
                              "traffic": "train", "chips": 1, "why": "test"}]
    for m in manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = []
    config = load(os.path.join(REHEARSAL, "configs", "tiny-gpt.json"))
    workload = load(os.path.join(REHEARSAL, "workloads",
                                 "tiny-gpt.train-1chip.json"))
    workload.update(name="wee-gpt.train", config="wee-gpt")
    root = str(tmp_path)

    def write(manifest=manifest, config=config, workload=workload):
        dump(config, root, "bm", "configs", "wee-gpt.json")
        dump(workload, root, "bm", "workloads", "wee-gpt.train.json")
        return dump(manifest, root, "BENCHMARK.json")

    return types.SimpleNamespace(root=root, manifest=manifest, config=config,
                                 workload=workload, write=write)


@pytest.mark.parametrize("workload,chips", [
    ("tiny-gpt.train-1chip", 1), ("tiny-gpt.train-tp2dp2", 4)])
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_end_to_end(workload, chips, trace):
    manifest_path = os.path.join(REHEARSAL, "BENCHMARK.json")
    proc = run_cell(manifest_path, workload, trace=trace, seed=2 ** 31 + 11)
    line = last_line(proc)
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 16
    assert line["device"]["count"] == chips
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    manifest = load(manifest_path)
    if trace:
        assert line["metrics"]["steady_recompiles"] == {
            "value": 0, "unit": "count"}
        names = {m["name"] for m in manifest["per_layer"]}
    else:
        names = {m["name"] for m in manifest["end_to_end"]}
        assert set(line["metrics"]) == names
    assert set(line["metrics"]) <= names
    # a CPU gives no device number: every clock or trace reading is null
    by_name = {m["name"]: m for m in
               manifest["per_layer"] + manifest["end_to_end"]}
    for name, metric in line["metrics"].items():
        assert metric["unit"] == by_name[name]["unit"]
        if by_name[name]["source"] != "program_counter":
            assert metric["value"] is None


def test_the_rehearsal_manifest_follows_the_real_one():
    """The tiny cells report the metrics of the real manifest, so a
    rehearsal drives every reader a chip run will."""
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    tiny = load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    assert tiny["end_to_end"] == real["end_to_end"]
    strip = [{k: v for k, v in m.items() if k != "workloads"}
             for m in real["per_layer"]]
    assert [{k: v for k, v in m.items() if k != "workloads"}
            for m in tiny["per_layer"]] == strip


def test_no_tpu_no_result():
    """The measuring path has no CPU fallback."""
    proc = run_cell(os.path.join(REHEARSAL, "BENCHMARK.json"),
                    "tiny-gpt.train-1chip", rehearse=False)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"metrics"' not in proc.stdout


def test_a_cell_is_added_as_files_alone(tmp_bench):
    """A new configuration, cell, reference and per-layer metric, in a
    directory run.py has never seen."""
    reader = os.path.join(tmp_bench.root, "bm", "layer_metrics",
                          "window_steps.py")
    os.makedirs(os.path.dirname(reader))
    with open(reader, "w") as f:
        f.write("def compute(observed):\n"
                "    return len(observed['spans']['dispatch_s'])\n")
    manifest = copy.deepcopy(tmp_bench.manifest)
    manifest["per_layer"].append({
        "name": "window_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "step builder",
        "moves": "train_tokens_per_s"})
    line = last_line(run_cell(tmp_bench.write(manifest=manifest),
                              "wee-gpt.train", trace=1))
    assert line["correct"] is True
    assert line["metrics"]["window_steps"]["value"] >= line["attempted"]


def test_perturbed_reference_makes_the_run_incorrect(tmp_bench):
    """The negative control: against a reference one of whose layers
    has other weights, the same run is not `correct`."""
    path = os.path.join(tmp_bench.root, "bm", "reference", "gpt2_off.py")
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as f:
        f.write(
            "from benchmarks.reference import gpt2\n\n\n"
            "def token_losses(params, *args, **kw):\n"
            "    params = dict(params)\n"
            "    block = dict(params['block1'])\n"
            "    fc2 = dict(block['fc2'])\n"
            "    fc2['weight'] = fc2['weight'] * 1.5\n"
            "    block['fc2'] = fc2\n"
            "    params['block1'] = block\n"
            "    return gpt2.token_losses(params, *args, **kw)\n")
    config = dict(tmp_bench.config, reference="gpt2_off")
    proc = run_cell(tmp_bench.write(config=config), "wee-gpt.train")
    line = last_line(proc)
    assert line["correct"] is False
    assert line["failed"] == 0   # the steps themselves were fine
    assert '"agrees": false' in proc.stdout


@pytest.mark.parametrize("kind,missing", [
    ("jobs", "no_such_job.py"), ("layer_metrics", "no_such_metric.py")])
def test_a_missing_file_is_named(tmp_bench, kind, missing):
    manifest = copy.deepcopy(tmp_bench.manifest)
    workload = dict(tmp_bench.workload)
    if kind == "jobs":
        workload["job"] = "no_such_job"
    else:
        manifest["per_layer"].append({
            "name": "no_such_metric", "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "train_tokens_per_s"})
    proc = run_cell(tmp_bench.write(manifest=manifest, workload=workload),
                    "wee-gpt.train", trace=1)
    assert proc.returncode != 0
    assert os.path.join(tmp_bench.root, "bm", kind, missing) in proc.stderr
    assert '"metrics"' not in proc.stdout
