"""The eight readers PR 36 added on the program's set-up ledger, in
`test_kda_stage_metrics.py`'s style: through the runner on a rehearsed
wee cell (counts on the CPU, null for what only a chip's clock gives),
on a program without the ledger, and the `{"phase": "setup_ledger"}`
line against the totals the readers give.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_setup_ledger_metrics.py -q`.
"""

import copy
import json
import sys

import pytest

from benchmarks.tests.test_owners import reader
from benchmarks.tests.test_rehearse import (  # noqa: F401
    last_line, run_cell, tmp_bench)

READERS = {
    "step_trace_s": ("s", "program_span", "entry and set-up"),
    "step_lower_s": ("s", "program_span", "entry and set-up"),
    "setup_other_programs_s": ("s", "program_span", "entry and set-up"),
    "setup_programs": ("count", "program_counter", "entry and set-up"),
    "setup_cache_misses": ("count", "program_counter", "entry and set-up"),
    "kernel_trace_s": ("s", "program_span", "kernels"),
    "kernel_call_sites": ("count", "program_counter", "kernels"),
    "kernel_body_eqns": ("count", "program_counter", "kernels"),
}
COUNTERS = [n for n, (_, source, _) in READERS.items()
            if source == "program_counter"]


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """(stdout lines as dicts, last line) of one traced rehearsal."""
    import types

    bench = tmp_bench.__wrapped__(tmp_path_factory.mktemp("ledger"))
    manifest = copy.deepcopy(bench.manifest)
    manifest["per_layer"] += [
        {"name": name, "unit": unit, "better": "lower", "source": source,
         "layer": layer, "moves": "setup_s"}
        for name, (unit, source, layer) in READERS.items()]
    proc = run_cell(bench.write(manifest=manifest), "wee-gpt.train", trace=1)
    line = last_line(proc)
    lines = [json.loads(text) for text in proc.stdout.splitlines()
             if text.startswith("{")]
    return types.SimpleNamespace(lines=lines, last=line)


def test_the_real_manifest_names_the_eight_as_this_file_does():
    import os

    from benchmarks.tests.test_rehearse import REPO, load

    real = {m["name"]: m for m in load(os.path.join(
        REPO, "BENCHMARK.json"))["per_layer"]}
    for name, (unit, source, layer) in READERS.items():
        assert real[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "setup_s"}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_rehearsal_reports_counts_and_no_clock(rehearsed, name):
    """run.py's rule: a CPU is not the device, so a `program_span`
    reading is null there; a count is a count anywhere."""
    assert rehearsed.last["correct"] is True
    metric = rehearsed.last["metrics"][name]
    assert metric["unit"] == READERS[name][0]
    if name in COUNTERS:
        assert isinstance(metric["value"], int)
    else:
        assert metric["value"] is None


def test_the_counts_are_the_wee_cells(rehearsed):
    metrics = rehearsed.last["metrics"]
    # weights, batches, the check, the state, the step: some dozen
    assert metrics["setup_programs"]["value"] >= 5
    assert metrics["setup_cache_misses"]["value"] == 0    # no cache on
    # off the chip every op takes its `jax.numpy` body: no call site
    # unless the environment forces the kernels
    sites = metrics["kernel_call_sites"]["value"]
    assert metrics["kernel_body_eqns"]["value"] >= sites >= 0


def test_the_line_parses_and_sums_to_the_readers_totals(rehearsed):
    (line,) = [r for r in rehearsed.lines if r.get("phase") == "setup_ledger"]
    totals = line["totals"]["setup"]
    stages = ("trace_s", "lower_s", "compile_s", "cache_read_s")
    everything = sum(totals[k] for k in stages)
    listed = sum(r.get(k) or 0.0 for r in line["programs_top"]
                 for k in stages) + line["programs_rest_s"]
    assert listed == pytest.approx(everything, rel=1e-9)
    assert line["programs_dropped"] == 0
    step = sum(r.get(k) or 0.0 for r in line["step"] for k in stages)
    assert {r["fun_name"] for r in line["step"]} == {"jit(local_step)"}
    assert 0 < step < everything
    # the step was traced, lowered and compiled once, before the window
    assert sum(1 for r in line["step"] if r.get("trace_s")) == 1
    assert all(not r["steady"] for r in line["step"])
    assert totals["programs"] == rehearsed.last["metrics"][
        "setup_programs"]["value"]
    assert sum(k["call_sites"] for k in line["kernels"].values()) == (
        rehearsed.last["metrics"]["kernel_call_sites"]["value"])
    # nothing fired between warm-up's end and the window's: the first
    # event after steady state was marked comes a window later
    assert line["events_at_steady"] <= line["events"]
    first = line["first_after_steady"]
    assert first is None or first["at_s"] - line["steady_at_s"] >= 1.0
    assert {s["name"] for s in line["spans"]} >= {
        "initialize_model_parallel", "init_sharded_optimizer",
        "make_tp_dp_train_step.build"}
    assert line["clock"] == "process_start" and line["armed_at_s"] > 0


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_finds_nothing_on_a_program_without_the_ledger(
        name, monkeypatch):
    """The parent commit has no `apex_tpu.monitor.compile.startup`."""
    import apex_tpu.monitor.compile

    monkeypatch.delattr(apex_tpu.monitor.compile, "startup", raising=False)
    monkeypatch.setitem(sys.modules, "apex_tpu.monitor.compile.startup",
                        None)
    observed = {}
    assert reader(name).compute(observed) is None
    assert observed["setup_ledger"] is None


def test_the_readers_on_a_ledger_by_hand():
    step = [{"fun_name": "jit(local_step)", "trace_s": 2.0, "lower_s": 0.5,
             "compile_s": 7.0, "steady": False},
            {"fun_name": "jit(local_step)", "cache_read_s": 0.25,
             "steady": False}]
    observed = {"setup_ledger": {
        "ledger": {"totals": {"setup": {"programs": 9, "cache_misses": 2}}},
        "step": step, "setup_s": 12.0, "step_s": 9.75,
        "step_kernels": {"a": {"call_sites": 3, "body_eqns": 30},
                         "b": {"call_sites": 1, "body_eqns": 7}},
        "step_kernel_spans": {"a": {"calls": 3, "trace_s": 0.5},
                              "b": {"calls": 1, "trace_s": 0.25}}}}
    got = {name: reader(name).compute(observed) for name in READERS}
    assert got == {
        "step_trace_s": 2.0, "step_lower_s": 0.5,
        "setup_other_programs_s": 2.25, "setup_programs": 9,
        "setup_cache_misses": 2, "kernel_trace_s": 0.75,
        "kernel_call_sites": 4, "kernel_body_eqns": 37}
