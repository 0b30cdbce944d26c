"""`lib/owners.py` and the readers built on it: on a pair recorded from
a chip run, and through the runner on the CPU.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_owners.py -q`.

The pair is from PR 25's traced run of `gpt2-medium.train-b12s1024` on
a v5e (seed 11): the first four executions of the step on device 0, cut
from the .xplane.pb by `trace.read_xplane`, and for each instruction in
them what `apex_tpu.monitor.scopes.step_owners()` said in that same
process.  The expected numbers were computed by this code when the pair
was recorded; a later PR that joins or sums another way fails here.
"""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks.lib import owners, trace  # noqa: E402
from benchmarks.tests.test_rehearse import (  # noqa: E402
    REHEARSAL, dump, last_line, load, run_cell)

RECORDED = os.path.join(HERE, "data", "owners_v5e_gpt2-medium_b12s1024")
OWNER_READERS = ("fwd_ms", "bwd_ms", "attn_sublayer_ms", "mlp_sublayer_ms",
                 "norm_ms", "head_loss_ms", "flash_bwd_ms", "flat_view_ms",
                 "dp_reduce_ms", "unowned_ms")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"_reader_{name}", os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def recorded():
    """(observed as the readers get it, the expected numbers)."""
    reduced = trace.reduce(trace.load_events(RECORDED + ".events.json.gz"))
    pair = trace.load_events(RECORDED + ".owner_map.json.gz")
    assert set(reduced["first"]["ops"]) <= set(pair["owners"])
    first = reduced["first"]
    rows = owners.join(first["ops"], first["n_steps"],
                       {k: tuple(v) for k, v in pair["owners"].items()},
                       pair["kernels"])
    return ({"trace": reduced, "owners": rows},
            load(RECORDED + ".expected.json"))


def test_owners_sum_to_the_busy_time(recorded):
    observed, want = recorded
    first = observed["trace"]["first"]
    assert first["n_steps"] == want["n_steps"] == 3
    busy_ms = 1e3 * first["busy_s"] / first["n_steps"]
    owned_ms = sum(row.ms for row in observed["owners"])
    assert owned_ms == pytest.approx(busy_ms, rel=1e-3)
    assert owned_ms == pytest.approx(want["owned_ms_per_step"], rel=1e-12)
    by_direction = {d: owners.ms(observed, direction=d + "$")
                    for d in ("fwd", "bwd", "step")}
    assert sum(by_direction.values()) == pytest.approx(owned_ms, rel=1e-12)


@pytest.mark.parametrize("name", OWNER_READERS)
def test_a_reader_returns_the_recorded_number(recorded, name):
    observed, want = recorded
    got = reader(name).compute(observed)
    assert got == pytest.approx(want["metrics"][name], rel=1e-12)


def test_flash_kernels_by_name_are_the_kernels_by_what_they_write(recorded):
    """`flash_attn_ms` knows the kernels by the size of what they write
    (`lib/hlo.py`), `flash_bwd_ms` by their names: forward plus
    backward by name is the same time."""
    observed, want = recorded
    by_size = {"trace": observed["trace"],
               "kernels": {"flash": want["flash_kernels_by_size"]}}
    assert reader("flash_attn_ms").compute(by_size) == pytest.approx(
        want["flash_attn_ms"], rel=1e-12)
    by_name = owners.ms(observed, name=r"flash_(fwd|bwd)")
    assert by_name == pytest.approx(want["flash_attn_ms"], abs=1e-9)
    assert owners.ms(observed, name=r"flash_fwd") + reader(
        "flash_bwd_ms").compute(observed) == pytest.approx(by_name)


def test_summary_has_the_four_kinds_by_owner_and_direction(recorded):
    observed, _ = recorded
    table = owners.summary(observed["owners"])
    assert set(table["block*/attn/flash"]) == {"fwd", "bwd"}
    assert table["block*/attn/flash"]["bwd"]["kernel"] == pytest.approx(
        reader("flash_bwd_ms").compute(observed))
    assert table["optimizer/adam"]["step"]["kernel"] > 0
    kinds = {k for by_dir in table.values() for by_kind in by_dir.values()
             for k in by_kind}
    assert kinds <= set(owners.KINDS) and {"kernel", "fusion", "copy"} <= kinds


@pytest.mark.parametrize("name,opcode,want", [
    ("flash_bwd.47", "custom-call", "kernel"),
    ("adam_flat", "custom-call", "kernel"),
    ("custom-call.59", "custom-call", "copy"),      # a ConcatBitcast
    ("fusion.2006", "fusion", "fusion"),
    ("all-reduce.3", "all-reduce", "collective"),
    ("all-gather-start.2", "all-gather-start", "collective"),
    ("reduce-scatter.7", "async-done", "collective"),   # known by its name
    ("copy-done.986", "copy-done", "copy"),
    ("slice-start.12", "slice-start", "copy"),
    ("ghost.1", "", "copy"),
])
def test_kind_of(name, opcode, want):
    assert owners.kind_of(name, opcode, ("flash_bwd", "adam_flat")) == want


def test_no_trace_or_no_scopes_is_none():
    """What the parent of PR 25 gives: nothing to read, nothing raised."""
    assert owners.table({"trace": None}) is None
    assert owners.ms({}, owner="unowned$") is None
    for name in OWNER_READERS:
        assert reader(name).compute({"trace": None}) is None


def test_the_rehearsal_prints_tuner_hit_pct_and_no_device_number(tmp_path):
    """A tiny cell on the CPU with the real manifest's per-layer
    metrics: the counter is a number, and no metric read from a device
    trace has a value."""
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    manifest = load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    cell = "tiny-gpt.train-tp2dp2"
    manifest["per_layer"] = [
        dict(m, workloads=[cell]) if "workloads" in m else m
        for m in real["per_layer"]]
    manifest["paths"] = [os.path.relpath(REHEARSAL, str(tmp_path))]
    manifest["configs"] = [dict(c, file=os.path.join(
        manifest["paths"][0], c["file"])) for c in manifest["configs"]]
    proc = run_cell(dump(manifest, str(tmp_path), "BENCHMARK.json"), cell,
                    trace=1)
    metrics = last_line(proc)["metrics"]
    assert 0.0 <= metrics["tuner_hit_pct"]["value"] <= 100.0
    assert metrics["tuner_hit_pct"]["unit"] == "%"
    for name in OWNER_READERS:
        assert metrics.get(name, {"value": None})["value"] is None
    assert '"phase": "owners"' not in proc.stdout
