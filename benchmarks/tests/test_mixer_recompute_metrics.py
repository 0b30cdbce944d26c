"""The two readers PR 38 added, in `test_kda_stage_metrics.py`'s style:
`mixer_recompute_ms` sums the rows of the owners' table that the
program's `scopes.step_rematted()` names and that stand under a block's
`attn`, and finds nothing on a program whose `scopes` has no such
function; `mixer_kept_gb` reads the model's counter; both through the
runner on the tiny hybrid cell.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_mixer_recompute_metrics.py -q`.
"""

import os

import pytest

from benchmarks.lib.owners import Row
from benchmarks.tests.test_hybrid_moe_rehearse import CELL, TINY, WORKLOAD
from benchmarks.tests.test_owners import reader
from benchmarks.tests.test_rehearse import dump, last_line, load, run_cell

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ROWS = [Row("fusion.1", "block1/attn/qkv", "bwd", "fusion", 4.0),
        Row("kda_locals_fwd.5", "block2/attn/scan", "bwd", "kernel", 5.5),
        # a loop's own event beside its body's instruction
        Row("while.3", "block2/attn/scan", "bwd", "copy", 1.25),
        Row("fusion.7", "block2/attn/scan", "bwd", "fusion", 1.0),
        # rematted, and not a mixer's
        Row("fusion.8", "block2/mlp/router", "bwd", "fusion", 0.5),
        # a mixer's backward that is no recomputation
        Row("fusion.9", "block1/attn/qkv", "bwd", "fusion", 8.0),
        Row("fusion.2", "block1/attn/qkv", "fwd", "fusion", 4.0)]
REMATTED = frozenset({"fusion.1", "kda_locals_fwd.5", "while.3", "fusion.7",
                      "fusion.8", "not_in_the_trace.1"})


def test_mixer_recompute_ms_is_the_rematted_rows_under_attn(monkeypatch):
    from apex_tpu.monitor import scopes

    monkeypatch.setattr(scopes, "step_rematted", lambda: REMATTED)
    compute = reader("mixer_recompute_ms").compute
    assert compute({"owners": ROWS}) == 4.0 + 5.5 + 1.0
    assert compute({"owners": [r for r in ROWS if r.direction == "fwd"]}) == 0
    assert compute({"owners": None}) is None         # no trace


def test_it_finds_nothing_on_a_program_that_cannot_say(monkeypatch):
    """The parent commit's `scopes` has no `step_rematted`."""
    from apex_tpu.monitor import scopes

    monkeypatch.delattr(scopes, "step_rematted")
    assert reader("mixer_recompute_ms").compute({"owners": ROWS}) is None


@pytest.mark.parametrize("stats,want", [
    ({"kept_bytes": 1_016_070_144}, 1.016070144),
    ({"kept_bytes": 0}, 0.0),
])
def test_mixer_kept_gb_reads_the_models_counter(stats, want, monkeypatch):
    from apex_tpu.models import hybrid_moe

    monkeypatch.setattr(hybrid_moe, "stats", lambda: dict(stats))
    assert reader("mixer_kept_gb").compute({}) == want


def test_it_finds_nothing_on_a_model_that_counts_nothing(monkeypatch):
    """The parent commit's `models/hybrid_moe.py` has no `stats`."""
    from apex_tpu.models import hybrid_moe

    monkeypatch.delattr(hybrid_moe, "stats")
    assert reader("mixer_kept_gb").compute({}) is None


@pytest.mark.parametrize("recompute", [True, False])
def test_the_tiny_cell_reports_what_its_mixers_keep(tmp_path, recompute):
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    mine = ("mixer_recompute_ms", "mixer_kept_gb")
    root = str(tmp_path)
    dump(TINY, root, "bm", "configs", "wee-hybrid.json")
    dump(dict(WORKLOAD, params=dict(WORKLOAD["params"],
                                    recompute_mixers=recompute)),
         root, "bm", "workloads", CELL + ".json")
    manifest = dump(dict(
        real, paths=["bm"],
        configs=[{"name": "wee-hybrid", "source": "none",
                  "file": "bm/configs/wee-hybrid.json", "reduced": [],
                  "why": "CPU rehearsal"}],
        workloads=[{"name": CELL, "config": "wee-hybrid", "traffic": "train",
                    "chips": 1, "why": "CPU rehearsal"}],
        per_layer=[dict(m, workloads=[CELL] if m["name"] in mine else [])
                   if "workloads" in m else m for m in real["per_layer"]]),
        root, "BENCHMARK.json")
    line = last_line(run_cell(manifest, CELL, trace=1, seed=2 ** 31 + 37))
    assert line["correct"] is True
    # a counter is a number on the CPU too: three KDA layers of 3 heads
    # x 8, rank 8, (2, 32) tokens, bf16 but beta's float32 logits
    tokens, wide, rank, heads = 2 * 32, 3 * 8, 8, 3
    kept = 3 * tokens * (2 * (8 * wide + 2 * rank) + 4 * heads)
    assert line["metrics"]["mixer_kept_gb"] == {
        "value": kept / 1e9 if recompute else 0.0, "unit": "GB"}
    # nothing is read from a device trace off the chip
    assert line["metrics"].get("mixer_recompute_ms",
                               {"value": None})["value"] is None
