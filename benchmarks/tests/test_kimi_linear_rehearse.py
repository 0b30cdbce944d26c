"""The `kimi_linear_train` job end to end on the CPU, through `run.py
--rehearse`, on the tiny preset under `data/kimi_rehearsal/` (its
manifest takes the metrics of the real one, so it cannot lag behind);
the boundary control; a negative control for `correct`; the packed
rows' generator; `lib/work_kimi_linear.py` against counts done by hand;
the new readers on rows by hand.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_kimi_linear_rehearse.py -q`.  No number printed
here is a measurement.
"""

import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks.jobs import kimi_linear_train  # noqa: E402
from benchmarks.lib import owners, work_kimi_linear as work  # noqa: E402
from benchmarks.tests.test_rehearse import (  # noqa: E402
    RESULT_KEYS, dump, last_line, load, run_cell)

PRESET = os.path.join(HERE, "data", "kimi_rehearsal")
CELL = "wee-kimi.train-packed"
REAL_CELL = "kimi-linear-48b-a3b.train-ep16share-packed-b1s8192"
NEW_METRICS = (
    "kda_scan_packed_ms", "kda_scan_packed_roofline_pct",
    "kda_glue_packed_ms", "latent_nope_proj_ms", "doc_boundary_ms",
    "flash_doc_scores_per_required", "docs_per_step",
    "kda_kernel_packed_pct")


@pytest.fixture
def bench(tmp_path):
    """write(reference=None) -> the path of the preset's manifest in a
    directory of its own, with the real manifest's metrics."""
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    root = str(tmp_path)
    shutil.copytree(os.path.join(PRESET, "configs"),
                    os.path.join(root, "bm", "configs"))
    shutil.copytree(os.path.join(PRESET, "workloads"),
                    os.path.join(root, "bm", "workloads"))
    manifest = load(os.path.join(PRESET, "BENCHMARK.json"))
    manifest.pop("note")
    manifest["end_to_end"] = real["end_to_end"]
    manifest["per_layer"] = [
        dict(m, workloads=[CELL] if REAL_CELL in m["workloads"] else [])
        if "workloads" in m else m for m in real["per_layer"]]

    def write(reference=None):
        if reference:
            config = load(os.path.join(PRESET, "configs", "wee-kimi.json"))
            dump(dict(config, reference=reference), root, "bm", "configs",
                 "wee-kimi.json")
        return dump(manifest, root, "BENCHMARK.json")

    write.root = root
    return write


def test_the_real_manifest_lists_the_new_readers_for_the_new_cell():
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    listed = {m["name"] for m in real["per_layer"]
              if m.get("workloads") == [REAL_CELL]}
    assert listed == set(NEW_METRICS)
    (cell,) = [w for w in real["workloads"] if w["name"] == REAL_CELL]
    assert cell["chips"] == 1 and cell["config"] == "kimi-linear-48b-a3b"
    for name in NEW_METRICS:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_end_to_end(bench, trace):
    proc = run_cell(bench(), CELL, trace=trace, seed=2 ** 31 + 11)
    line = last_line(proc)
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 16
    # the boundary control fails, as it must
    (control,) = [json.loads(r) for r in proc.stdout.splitlines()
                  if '"phase": "boundary_control"' in r]
    assert control["fails_as_it_must"] is True
    assert '"phase": "scan_check"' in proc.stdout
    assert '"delta_rule": {"calls": 4, "chunk": 64' in proc.stdout
    if not trace:
        assert set(line["metrics"]) == {
            "train_tokens_per_s", "loss_after_16_steps", "setup_s"}
        assert all(m["value"] is None for m in line["metrics"].values())
        return
    metrics = line["metrics"]
    # every new reader returns a number or nothing: the counters are
    # numbers, nothing read from a device trace is
    (docs,) = [json.loads(r) for r in proc.stdout.splitlines()
               if '"phase": "documents"' in r]
    assert metrics["docs_per_step"]["value"] == docs["docs_per_step"] > 1
    # off the chip no call takes the kernels, and none of flash's runs
    assert metrics["kda_kernel_packed_pct"]["value"] == 0.0
    assert "flash_doc_scores_per_required" not in metrics
    for name in ("kda_scan_packed_ms", "kda_scan_packed_roofline_pct",
                 "kda_glue_packed_ms", "latent_nope_proj_ms",
                 "doc_boundary_ms", "flash_attn_ms", "unowned_ms"):
        assert metrics.get(name, {"value": None})["value"] is None
    assert metrics["steady_recompiles"]["value"] == 0
    # the older cells' listed readers are not this cell's
    assert not {"moe_router_ms", "mtp_ms", "mla_proj_ms", "kda_scan_ms"} \
        & set(metrics)


def test_a_reference_that_resets_nowhere_makes_the_run_incorrect(bench):
    """The negative control: against a reference that never heard of a
    document the same run is not `correct`, by the reference check (the
    scan check is held to the same reference's recurrence, which still
    resets: it passes)."""
    path = os.path.join(bench.root, "bm", "reference", "no_documents.py")
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as f:
        f.write(
            "from benchmarks.reference import kimi_linear as ref\n\n"
            "scan_outputs = ref.scan_outputs\n\n\n"
            "def token_losses(*args, boundaries=True, **kw):\n"
            "    return ref.token_losses(*args, boundaries=False, **kw)\n")
    proc = run_cell(bench("no_documents"), CELL)
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    records = [r for r in proc.stdout.splitlines() if '"agrees"' in r]
    assert any('"phase": "reference"' in r and '"agrees": false' in r
               for r in records), records
    assert any('"phase": "scan_check"' in r and '"agrees": true' in r
               for r in records), records


# ----------------------------- the packed rows -----------------------------

LENGTHS = {"median": 512, "sigma": 1.0, "min": 32, "max": 8192}


def test_packed_rows_are_documents_end_to_end():
    rows, lie = kimi_linear_train.packed_rows(
        np.random.default_rng(7), 10, 8192, 20480, 20479, LENGTHS)
    assert rows.shape == (10, 8192) and rows.dtype == np.int32
    assert 0 <= rows.min() and rows.max() == 20479
    for row, lengths in zip(rows, lie):
        assert sum(lengths) == 8192
        ends = np.cumsum(lengths)
        # an EOD at each document's last position and nowhere else; the
        # last is cut at the row's end and closes only by chance
        where = np.flatnonzero(row == 20479) + 1
        assert set(where) in (set(ends), set(ends[:-1]))
        assert all(32 <= n <= 8192 for n in lengths[:-1])
        assert work.document_lengths([row], 20479) == lengths
    every = [n for row in lie for n in row]
    assert 5 <= len(every) / 10 <= 16            # about ten a row
    # each row draws its own
    assert lie[0] != lie[1]
    again, _ = kimi_linear_train.packed_rows(
        np.random.default_rng(7), 10, 8192, 20480, 20479, LENGTHS)
    np.testing.assert_array_equal(rows, again)


def test_ids_skip_an_eod_in_the_middle_of_the_vocabulary():
    rows, lie = kimi_linear_train.packed_rows(
        np.random.default_rng(3), 4, 512, 16, 5,
        {"median": 40, "sigma": 0.5, "min": 8, "max": 512})
    assert set(np.unique(rows)) == set(range(16))
    for row, lengths in zip(rows, lie):
        assert work.document_lengths([row], 5) == lengths


# ---------------------- lib/work_kimi_linear.py by hand ----------------------

@pytest.fixture(scope="module")
def kimi():
    return work.sizes(load(os.path.join(
        BENCH, "configs", "kimi-linear-48b-a3b.json")))


def test_the_share_by_hand(kimi):
    assert (kimi["layers"], kimi["attention"], kimi["kda"]) == (5, 1, 4)
    assert kimi["attends"] == (3,) and kimi["dense"] == 1
    assert kimi["expert_layers"] == 4
    assert (kimi["held"], kimi["published"], kimi["top_k"]) == (16, 256, 8)
    assert (kimi["vocab"], kimi["eod"]) == (20480, 20479)


def test_the_configuration_keeps_every_published_width():
    config = load(os.path.join(BENCH, "configs", "kimi-linear-48b-a3b.json"))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f]
    (row,) = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    assert config["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 20480}
    assert set(config["reduced"]) == set(reduced)
    for key, value in row["config"].items():
        assert config[key] == reduced.get(key, value), key
        if key in reduced:
            assert config[key + "_published"] == value


def test_parameters_of_the_share_by_hand(kimi):
    counts = work.param_counts(kimi)
    # q, k, v, o at 2304 x 4096; two rank-128 pairs; W_beta; three 4-tap
    # convolutions, A_h, dt_bias, the output norm
    assert counts["kda"] == (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
        + 3 * 4 * 4096 + 32 + 4096 + 128) == 39_514_272
    # W_q 2304 x 32 x 192, W_kva 2304 x 576, W_kvb 512 x 32 x 256, W_o,
    # the inner norm
    assert counts["latent"] == (
        14_155_776 + 1_327_104 + 4_194_304 + 9_437_184 + 512) == 29_114_880
    assert counts["dense_mlp"] == 3 * 2304 * 9216 == 63_700_992
    assert counts["expert_layer"] == (
        17 * 3 * 2304 * 1024 + 2304 * 256 + 256) == 120_914_176
    assert counts["embed_and_head"] == 2 * 20480 * 2304
    assert counts["total"] == (
        4 * 39_514_272 + 29_114_880 + 63_700_992 + 4 * 120_914_176
        + 94_371_840 + 5 * 4608 + 2304) == 828_926_848


def test_required_flops_a_token_by_hand(kimi):
    parts = work.forward_flops_per_token(kimi, 1150.0)
    assert parts["kda_projections"] == 4 * 2 * (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    assert parts["latent_projections"] == 2 * 29_114_368
    assert parts["attention"] == 32 * 1150.0 * 2 * (192 + 128)
    assert parts["scan"] == 4 * 32 * 7 * 128 * 128
    assert parts["dense_mlp"] == 2 * 63_700_992
    assert parts["router"] == 4 * 2 * 2304 * 256
    assert parts["shared_expert"] == 4 * 2 * 3 * 2304 * 1024
    # 8 of 256 chosen, 16 held: half an expert a token
    assert parts["held_experts"] == 4 * 2 * 3 * 2304 * 1024 * 0.5
    assert parts["head"] == 2 * 2304 * 20480
    total = sum(parts.values())
    assert 0.72e9 < total < 0.73e9
    assert work.train_flops_per_token(kimi, 1150.0) == 3 * total


def test_the_kept_pairs_are_the_documents_triangles():
    assert work.document_lengths([[1, 2, 9, 3, 9, 9, 4]], 9) == [3, 2, 1, 1]
    assert work.document_lengths([[1, 9], [9, 2]], 9) == [2, 1, 1]
    assert work.kept_pairs([3, 2, 1, 1]) == 6 + 3 + 1 + 1
    # one document a row is the causal half square
    assert work.kept_pairs([8192]) == 8192 * 8193 // 2


def test_flash_and_scan_work_by_hand(kimi):
    pairs = work.kept_pairs([4096, 4096])
    got = work.flash_attention_work(kimi, pairs, 8192)
    assert got["flops"] == 3 * 2 * (192 + 128) * pairs * 32
    assert got["bytes"] == 6 * (192 + 128) * 32 * 8192 * 2
    scan = work.scan_work(kimi, 1, 8192)
    assert scan["flops"] == 4 * 3 * 8192 * 32 * 7 * 128 * 128
    inputs = 3 * 128 * 2 + 128 * 4 + 4
    assert scan["bytes"] == 4 * 8192 * 32 * (
        (inputs + 256) + (inputs + 256) + inputs)
    gemm = work.expert_gemm_work(kimi, 8192)
    assert gemm["rows"] == 8192 * 8 * 16 / 256 == 4096
    assert gemm["flops"] == 4 * 3 * 4096 * 2 * 3 * 2304 * 1024


# ----------------------- the readers, on rows by hand -----------------------

def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"_reader_{name}", os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _observed(rows, **more):
    return dict({"owners": [owners.Row(*r) for r in rows]}, **more)


ROWS = [
    ("fusion.1", "block0/attn/scan", "fwd", "fusion", 2.0),
    ("kda_locals_bwd.7", "block4/attn/scan", "bwd", "kernel", 3.0),
    ("while.3", "block2/attn/scan", "bwd", "copy", 10.0),
    ("fusion.2", "block1/attn/conv", "fwd", "fusion", 0.5),
    ("fusion.3", "block2/attn/decay", "bwd", "fusion", 0.25),
    ("fusion.4", "block4/attn/onorm", "bwd", "fusion", 0.125),
    ("fusion.5", "block0/attn/segments", "fwd", "fusion", 0.0625),
    ("fusion.6", "block3/attn/q", "fwd", "fusion", 4.0),
    ("fusion.7", "block3/attn/kv_a", "fwd", "fusion", 1.0),
    ("fusion.8", "block3/attn/kv_b", "bwd", "fusion", 2.0),
    ("rope_stage.1", "block3/attn/stage", "fwd", "kernel", 0.5),
    ("fusion.9", "block3/attn/proj", "fwd", "fusion", 3.0),
    # a KDA layer's output projection is not the latent layer's
    ("fusion.10", "block1/attn/proj", "fwd", "fusion", 20.0),
    ("flash_fwd.1", "block3/attn/flash", "fwd", "kernel", 8.0),
]


def test_the_new_readers_on_rows_by_hand():
    observed = _observed(
        ROWS, work={"scan": {"flops": 197e12 * 1e-3, "bytes": 819e9 * 2e-3}},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"flash_scores_computed": 300, "flash_scores_required": 200,
                  "doc_pairs_share": 0.25, "docs_per_step": 9.5})
    assert _reader("kda_scan_packed_ms").compute(observed) == 5.0
    assert _reader("kda_scan_packed_roofline_pct").compute(observed) == 40.0
    assert _reader("kda_glue_packed_ms").compute(observed) == 0.875
    assert _reader("latent_nope_proj_ms").compute(observed) == 10.5
    assert _reader("doc_boundary_ms").compute(observed) == 0.0625
    # 1.5 computed for each pair a causal mask keeps, a quarter of which
    # the documents keep
    assert _reader("flash_doc_scores_per_required").compute(observed) == 6.0
    assert _reader("docs_per_step").compute(observed) == 9.5


def test_the_new_readers_find_nothing_on_another_program():
    """A program without the scopes or the counters, or a run without
    a trace: None, not 0 and not an exception."""
    other = _observed([r for r in ROWS if "flash" in r[1]], work={},
                      peaks=None)
    for name in NEW_METRICS:
        if name == "kda_kernel_packed_pct":
            continue      # reads the program's live counter
        assert _reader(name).compute(other) is None, name
        assert _reader(name).compute({"owners": None}) is None, name
