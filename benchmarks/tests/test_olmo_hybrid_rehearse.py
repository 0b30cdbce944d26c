"""The `olmo_hybrid_train` job end to end on the CPU, through `run.py
--rehearse`, on the tiny preset under `data/olmo_rehearsal/` (its
manifest takes the metrics of the real one, so it cannot lag behind);
the boundary control; a negative control for `correct`; the
configuration against the published sizes; `lib/work_olmo_hybrid.py`
against counts done by hand; the five new readers on rows by hand.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_olmo_hybrid_rehearse.py -q`.  No number printed
here is a measurement.
"""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks.lib import owners, work_olmo_hybrid as work  # noqa: E402
from benchmarks.tests.test_rehearse import (  # noqa: E402
    RESULT_KEYS, dump, last_line, load, run_cell)

PRESET = os.path.join(HERE, "data", "olmo_rehearsal")
CELL = "wee-olmo.train-packed"
REAL_CELL = "olmo-hybrid-7b.train-pp8stage-packed-b1s8192"
NEW_METRICS = ("gdn_scan_ms", "gdn_scan_roofline_pct", "gdn_kernel_pct",
               "gdn_lane_fill_pct", "gdn_glue_ms")


@pytest.fixture
def bench(tmp_path):
    """write(reference=None) -> the path of the preset's manifest in a
    directory of its own, with the real manifest's metrics."""
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    root = str(tmp_path)
    for part in ("configs", "workloads"):
        shutil.copytree(os.path.join(PRESET, part),
                        os.path.join(root, "bm", part))
    manifest = load(os.path.join(PRESET, "BENCHMARK.json"))
    manifest.pop("note")
    manifest["end_to_end"] = real["end_to_end"]
    manifest["per_layer"] = [
        dict(m, workloads=[CELL] if REAL_CELL in m["workloads"] else [])
        if "workloads" in m else m for m in real["per_layer"]]

    def write(reference=None):
        if reference:
            config = load(os.path.join(PRESET, "configs", "wee-olmo.json"))
            dump(dict(config, reference=reference), root, "bm", "configs",
                 "wee-olmo.json")
        return dump(manifest, root, "BENCHMARK.json")

    write.root = root
    return write


def test_the_real_manifest_lists_the_new_readers_for_the_new_cell():
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    listed = {m["name"] for m in real["per_layer"]
              if m.get("workloads") == [REAL_CELL]}
    assert listed == set(NEW_METRICS)
    (cell,) = [w for w in real["workloads"] if w["name"] == REAL_CELL]
    assert cell["chips"] == 1 and cell["config"] == "olmo-hybrid-7b"
    (config,) = [c for c in real["configs"] if c["name"] == "olmo-hybrid-7b"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    for name in NEW_METRICS:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_end_to_end(bench, trace):
    proc = run_cell(bench(), CELL, trace=trace, seed=2 ** 31 + 13)
    line = last_line(proc)
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 16
    (control,) = [json.loads(r) for r in proc.stdout.splitlines()
                  if '"phase": "boundary_control"' in r]
    assert control["fails_as_it_must"] is True
    assert '"phase": "scan_check"' in proc.stdout
    (first,) = [json.loads(r) for r in proc.stdout.splitlines()
                if '"phase": "first_step"' in r]
    assert first["agrees"] is True and first["change_gap"] < 0.05
    (compiled,) = [json.loads(r) for r in proc.stdout.splitlines()
                   if '"phase": "compile"' in r]
    # three Gated DeltaNet layers, one decay a head each
    assert compiled["delta_rule"]["calls"] == 3
    assert compiled["delta_rule"]["scalar_calls"] == 3
    assert compiled["conv_stage"]["calls"] == 3
    if not trace:
        assert set(line["metrics"]) == {
            "train_tokens_per_s", "loss_after_16_steps", "setup_s"}
        assert all(m["value"] is None for m in line["metrics"].values())
        return
    metrics = line["metrics"]
    # the counters are numbers off the chip too; nothing read from a
    # device trace is
    assert metrics["gdn_kernel_pct"]["value"] == 0.0
    # keys 8 and values 16 wide: a float32 state of 8 x 16 in 8 x 128
    assert metrics["gdn_lane_fill_pct"]["value"] == 12.5
    for name in ("gdn_scan_ms", "gdn_scan_roofline_pct", "gdn_glue_ms",
                 "flash_attn_ms", "norm_ms", "unowned_ms"):
        assert metrics.get(name, {"value": None})["value"] is None
    assert metrics["steady_recompiles"]["value"] == 0
    # the other cells' listed readers are not this cell's
    assert not {"kda_scan_ms", "kda_scan_packed_ms", "moe_router_ms",
                "docs_per_step", "mixer_kept_gb"} & set(metrics)


def test_a_reference_that_resets_nowhere_makes_the_run_incorrect(bench):
    """The negative control: against a reference that never heard of a
    document the same run is not `correct`, by the reference check (the
    scan check is held to the same reference's recurrence, which still
    resets: it passes)."""
    path = os.path.join(bench.root, "bm", "reference", "no_documents.py")
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as f:
        f.write(
            "from benchmarks.reference import olmo_hybrid as ref\n\n"
            "scan_outputs = ref.scan_outputs\n"
            "loss = ref.loss\n\n\n"
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


def test_a_reference_whose_gradient_is_off_makes_the_run_incorrect(bench):
    """The first step's check: against a reference whose loss is the
    negative of its own (its per-token losses as they were, so the
    forward checks pass) the gradient reads about 2 off and the run is
    not `correct`."""
    path = os.path.join(bench.root, "bm", "reference", "steeper.py")
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as f:
        f.write(
            "from benchmarks.reference import olmo_hybrid as ref\n\n"
            "scan_outputs = ref.scan_outputs\n"
            "token_losses = ref.token_losses\n\n\n"
            "def loss(*args, **kw):\n"
            "    return -ref.loss(*args, **kw)\n")
    proc = run_cell(bench("steeper"), CELL)
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    (first,) = [json.loads(r) for r in proc.stdout.splitlines()
                if '"phase": "first_step"' in r]
    assert first["agrees"] is False
    assert first["grad_gap"] > 1.5
    assert '"phase": "reference", ' in proc.stdout
    assert '"agrees": true' in [r for r in proc.stdout.splitlines()
                                if '"phase": "reference"' in r][0]


# ------------------------ the configuration, by hand ------------------------

@pytest.fixture(scope="module")
def olmo():
    return work.sizes(load(os.path.join(BENCH, "configs",
                                        "olmo-hybrid-7b.json")))


# the source's config.json keys of sizes, as published
PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "max_position_embeddings": 65536,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"]}


def test_the_configuration_keeps_every_published_width():
    config = load(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json"))
    assert config["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    reduced = {"num_hidden_layers": 4, "vocab_size": 25088}
    assert set(config["reduced"]) == set(reduced)
    assert config["layer_types"] == PUBLISHED["layer_types"] * 8
    for key, value in PUBLISHED.items():
        if key == "layer_types":
            continue
        assert config[key] == reduced.get(key, value), key
        if key in reduced:
            assert config[key + "_published"] == value


def test_the_share_by_hand(olmo):
    assert (olmo["layers"], olmo["attention"], olmo["gdn"]) == (4, 1, 3)
    assert olmo["attends"] == (3,)
    assert (olmo["heads"], olmo["head_dim"]) == (30, 128)
    assert (olmo["gdn_heads"], olmo["key_dim"], olmo["value_dim"]) == (
        30, 96, 192)
    assert (olmo["vocab"], olmo["eod"]) == (25088, 25087)
    assert olmo["vocab"] * 4 == 100352


def test_parameters_of_the_share_by_hand(olmo):
    counts = work.param_counts(olmo)
    # W_q, W_k 3840 x 2880; W_v, W_g, W_o^T 3840 x 5760; W_a, W_b 3840 x
    # 30; three 4-tap convolutions; A_log and dt_bias; the 192-wide norm
    assert counts["gdn"] == (2 * 3840 * 2880 + 3 * 3840 * 5760
                             + 2 * 3840 * 30 + 4 * (2880 + 2880 + 5760)
                             + 60 + 192) == 88_750_332
    assert counts["attention"] == 4 * 3840 ** 2 + 2 * 3840 == 58_990_080
    assert counts["dense_mlp"] == 3 * 3840 * 11008 == 126_812_160
    assert counts["embed_and_head"] == 2 * 25088 * 3840
    period = 3 * (88_750_332 + 126_812_160 + 7680) + (
        58_990_080 + 126_812_160 + 7680)
    assert period == 832_520_436
    assert counts["total"] == period + 192_675_840 + 3840 == 1_025_200_116


def test_required_flops_a_token_by_hand(olmo):
    parts = work.forward_flops_per_token(olmo, 1150.0)
    assert parts["gdn_projections"] == 3 * 2 * (
        2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30)
    assert parts["scan"] == 3 * 30 * 7 * 96 * 192
    assert parts["attention_projections"] == 2 * 4 * 3840 ** 2
    assert parts["attention"] == 30 * 1150.0 * 2 * 2 * 128
    assert parts["dense_mlp"] == 4 * 2 * 3 * 3840 * 11008
    assert parts["head"] == 2 * 3840 * 25088
    assert work.train_flops_per_token(olmo, 1150.0) == 3 * sum(
        parts.values())
    # 5.6 GFLOP a token trained
    assert 5.6e9 < work.train_flops_per_token(olmo, 1150.0) < 5.7e9


def test_flash_and_scan_work_by_hand(olmo):
    pairs = work.kept_pairs([4096, 4096])
    got = work.flash_attention_work(olmo, pairs, 8192)
    assert got["flops"] == 3 * 2 * 2 * 128 * pairs * 30
    assert got["bytes"] == 6 * 2 * 128 * 30 * 8192 * 2
    scan = work.scan_work(olmo, 1, 8192)
    assert scan["flops"] == 3 * 3 * 8192 * 30 * 7 * 96 * 192
    inputs = (96 + 96 + 192) * 2 + 4 + 4
    assert scan["bytes"] == 3 * 8192 * 30 * (
        (inputs + 384) + (inputs + 384) + inputs)


# ----------------------- the readers, on rows by hand -----------------------

def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"_reader_{name}", os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ROWS = [
    ("fusion.1", "block0/attn/scan", "fwd", "fusion", 2.0),
    ("kda_locals_bwd.7", "block2/attn/scan", "bwd", "kernel", 3.0),
    ("while.3", "block1/attn/scan", "bwd", "copy", 10.0),
    ("conv_stage.1", "block1/attn/conv", "fwd", "kernel", 0.5),
    ("fusion.3", "block2/attn/decay", "bwd", "fusion", 0.25),
    ("fusion.4", "block0/attn/onorm", "bwd", "fusion", 0.125),
    # the projections' GEMMs are not glue
    ("fusion.5", "block0/attn/gate", "fwd", "fusion", 4.0),
    ("fusion.6", "block1/attn/qkv", "fwd", "fusion", 6.0),
    ("fusion.7", "block2/attn/proj", "bwd", "fusion", 3.0),
    ("fusion.8", "block3/attn/qknorm", "fwd", "fusion", 0.0625),
    ("flash_fwd.1", "block3/attn/flash", "fwd", "kernel", 8.0),
]


def test_the_new_device_readers_on_rows_by_hand():
    observed = {
        "owners": [owners.Row(*r) for r in ROWS],
        "work": {"scan": {"flops": 197e12 * 1e-3, "bytes": 819e9 * 2e-3}},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert _reader("gdn_scan_ms").compute(observed) == 5.0
    assert _reader("gdn_scan_roofline_pct").compute(observed) == 40.0
    assert _reader("gdn_glue_ms").compute(observed) == 0.875


def test_the_new_counters_read_the_ops_stats():
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import delta_rule

    delta_rule.reset_stats()
    assert _reader("gdn_kernel_pct").compute({}) is None
    assert _reader("gdn_lane_fill_pct").compute({}) is None
    q = jnp.zeros((1, 2, 64, 96))
    v = jnp.zeros((1, 2, 64, 192))
    beta = jnp.zeros((1, 2, 64))
    for kernels in (True, None):
        jax.eval_shape(lambda q, v, b: delta_rule.gated_delta_rule(
            q, q, v, b, b, chunk=32, use_pallas_override=kernels), q, v, beta)
    assert _reader("gdn_kernel_pct").compute({}) == 50.0
    # 96 x 192 of the 96 x 256 the (8, 128) tiles hold
    assert _reader("gdn_lane_fill_pct").compute({}) == 75.0
    delta_rule.reset_stats()


def test_the_new_readers_find_nothing_on_another_program():
    """A program without the scopes, or a run without a trace: None, not
    0 and not an exception."""
    other = {"owners": [owners.Row(*r) for r in ROWS if "flash" in r[1]],
             "work": {}, "peaks": None}
    for name in ("gdn_scan_ms", "gdn_scan_roofline_pct", "gdn_glue_ms"):
        assert _reader(name).compute(other) is None, name
        assert _reader(name).compute({"owners": None}) is None, name
