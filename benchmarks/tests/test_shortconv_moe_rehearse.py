"""The `shortconv_moe_train` job end to end on the CPU, through `run.py
--rehearse`, on the tiny preset under `data/shortconv_rehearsal/` (its
manifest takes the metrics of the real one, so it cannot lag behind);
the taps control; a negative control for `correct`;
`lib/work_shortconv_moe.py` against counts done by hand; the
configuration against the catalog; the new readers on the rows recorded
from a traced chip run of the real cell.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_shortconv_moe_rehearse.py -q`.  No number printed
here is a measurement.
"""

import gzip
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks.lib import owners, work_shortconv_moe as work  # noqa: E402
from benchmarks.tests.test_rehearse import (  # noqa: E402
    RESULT_KEYS, dump, last_line, load, run_cell)

PRESET = os.path.join(HERE, "data", "shortconv_rehearsal")
CELL = "wee-lfm2.train"
REAL_CELL = "lfm2-8b-a1b.train-ep2share-b1s8192"
NEW_METRICS = (
    "shortconv_gate_ms", "shortconv_gate_hbm_pct", "shortconv_proj_ms",
    "gqa_rope_proj_ms", "top4_expert_gemm_ms",
    "top4_expert_gemm_roofline_pct", "top4_dispatch_ms", "top4_router_ms",
    "top4_load_imbalance", "top4_overflow_assignments")
# the joined rows of a traced run of the real cell on a v5e (my chip
# run, PR 39), what `top4_expert_gemm_ms` took back for the experts'
# scope in that process, and what the readers said there
RECORDED = os.path.join(HERE, "data", "owners_v5e_lfm2-8b-a1b_b1s8192.json.gz")


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
            config = load(os.path.join(PRESET, "configs", "wee-lfm2.json"))
            dump(dict(config, reference=reference), root, "bm", "configs",
                 "wee-lfm2.json")
        return dump(manifest, root, "BENCHMARK.json")

    write.root = root
    return write


def test_the_real_manifest_lists_the_new_readers_for_the_new_cell():
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    listed = {m["name"] for m in real["per_layer"]
              if m.get("workloads") == [REAL_CELL]}
    assert listed == set(NEW_METRICS)
    (cell,) = [w for w in real["workloads"] if w["name"] == REAL_CELL]
    assert cell["chips"] == 1 and cell["config"] == "lfm2-8b-a1b"
    assert len(cell["why"]) <= 200
    workload = load(os.path.join(BENCH, "workloads", REAL_CELL + ".json"))
    assert workload["params"] == {
        "batch": 1, "seq": 8192, "tensor_parallel": 1,
        "sequence_parallel": False, "state_dtype": "bfloat16", "lr": 1e-05}
    for name in NEW_METRICS:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_end_to_end(bench, trace):
    proc = run_cell(bench(), CELL, trace=trace, seed=2 ** 31 + 39)
    line = last_line(proc)
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 16
    # the taps control fails, as it must
    (control,) = [json.loads(r) for r in proc.stdout.splitlines()
                  if '"phase": "taps_control"' in r]
    assert control["fails_as_it_must"] is True
    # the gate alone, held to the reference's float32 scoring
    (router,) = [json.loads(r) for r in proc.stdout.splitlines()
                 if '"phase": "router_check"' in r]
    assert router["agrees"] is True and router["flipped"] == 0.0
    assert router["weight_gap"] < 1e-6
    (window,) = [json.loads(r) for r in proc.stdout.splitlines()
                 if '"phase": "window"' in r]
    # four expert layers, experts [4, 12) of 16, 3 of 64 tokens' 192
    # assignments a layer to all sixteen
    assert len(window["moe_counts"]) == 4
    assert all(len(row) == 8 for row in window["moe_counts"])
    assert window["held_rows_per_step"] == [
        sum(row) for row in window["moe_counts"]]
    assert all(0 < rows <= 192 for rows in window["held_rows_per_step"])
    assert window["moe_overflow"] == [0, 0, 0, 0]
    if not trace:
        assert set(line["metrics"]) == {
            "train_tokens_per_s", "loss_after_16_steps", "setup_s"}
        assert all(m["value"] is None for m in line["metrics"].values())
        return
    metrics = line["metrics"]
    # the counters are numbers, nothing read from a device trace is
    assert metrics["top4_overflow_assignments"]["value"] == 0
    assert metrics["top4_load_imbalance"]["value"] == max(
        max(row) * 8 / sum(row) for row in window["moe_counts"])
    assert metrics["steady_recompiles"]["value"] == 0
    for name in ("shortconv_gate_ms", "shortconv_gate_hbm_pct",
                 "shortconv_proj_ms", "gqa_rope_proj_ms",
                 "top4_expert_gemm_ms", "top4_expert_gemm_roofline_pct",
                 "top4_dispatch_ms", "top4_router_ms", "flash_attn_ms",
                 "unowned_ms"):
        assert metrics.get(name, {"value": None})["value"] is None
    # the older cells' listed readers are not this cell's
    assert not {"moe_router_ms", "mtp_ms", "mla_proj_ms", "kda_scan_ms",
                "kda_conv_ms", "mixer_recompute_ms"} & set(metrics)


def test_a_reference_that_turns_its_taps_makes_the_run_incorrect(bench):
    """The negative control: against a reference whose convolutions
    read the taps the other way round the same run is not `correct`,
    by the reference check (and the control, turned back, agrees)."""
    path = os.path.join(bench.root, "bm", "reference", "turned_taps.py")
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as f:
        f.write(
            "from benchmarks.reference import lfm2_moe as ref\n\n"
            "router_weights = ref.router_weights\n\n\n"
            "def token_losses(*args, taps_reversed=False, **kw):\n"
            "    return ref.token_losses(\n"
            "        *args, taps_reversed=not taps_reversed, **kw)\n")
    proc = run_cell(bench("turned_taps"), CELL)
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    assert any('"phase": "reference"' in r and '"agrees": false' in r
               for r in proc.stdout.splitlines())
    assert any('"phase": "taps_control"' in r
               and '"fails_as_it_must": false' in r
               for r in proc.stdout.splitlines())


def test_a_router_that_scores_in_bf16_fails_the_router_check():
    """What the per-token losses cannot see: the reference's own gate
    with its scores rounded to bf16 reads outside both bounds, the
    program's float32 gate far inside them."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.shortconv_moe import ShortConvMoE
    from benchmarks.jobs import shortconv_moe_train as job
    from benchmarks.reference import lfm2_moe as ref

    config = load(os.path.join(PRESET, "configs", "wee-lfm2.json"))
    model = ShortConvMoE(job.model_config(
        dict(config, initializer_range=0.5), dtype=jnp.bfloat16))
    params = model.init(jax.random.PRNGKey(0))
    device = jax.devices()[0]
    own = job.router_gap(model, params, ref, config, 4096, 3, device)
    assert own["flipped"] <= job.ROUTER_FLIPPED_TOL / 4
    assert own["weight_gap"] <= job.ROUTER_WEIGHT_TOL / 100
    rounded = job.router_gap(model, params, ref, config, 4096, 3, device,
                             router_dtype=jnp.bfloat16)
    assert rounded["flipped"] > 4 * job.ROUTER_FLIPPED_TOL
    assert rounded["weight_gap"] > 4 * job.ROUTER_WEIGHT_TOL
    # the weights of a row add up to the scaling factor, on both sides
    dense = ref.router_weights(
        params["block2"]["mlp"], jnp.ones((5, 32), jnp.bfloat16),
        arch=config)
    assert dense.shape == (5, 16)
    assert (dense > 0).sum(axis=-1).tolist() == [3] * 5
    assert jnp.allclose(dense.sum(axis=-1), 1.0, atol=1e-6)


# -------------------- lib/work_shortconv_moe.py by hand --------------------

@pytest.fixture(scope="module")
def lfm2():
    return work.sizes(load(os.path.join(BENCH, "configs",
                                        "lfm2-8b-a1b.json")))


def test_the_share_by_hand(lfm2):
    assert (lfm2["layers"], lfm2["conv"], lfm2["attention"]) == (6, 5, 1)
    assert lfm2["kinds"] == ("conv", "conv", "attention", "conv", "conv",
                             "conv")
    assert (lfm2["dense"], lfm2["expert_layers"]) == (2, 4)
    assert (lfm2["held"], lfm2["published"], lfm2["top_k"]) == (16, 32, 4)
    assert (lfm2["heads"], lfm2["kv_heads"], lfm2["head_dim"]) == (32, 8, 64)
    assert (lfm2["vocab"], lfm2["tied"], lfm2["taps"]) == (32768, True, 3)


def test_the_configuration_keeps_every_published_width():
    config = load(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json"))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f]
    (row,) = [r for r in rows if r["name"] == "LFM2-8B-A1B"]
    assert config["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 6, "num_experts": 16, "vocab_size": 32768,
               "layer_types": row["config"]["layer_types"][:6]}
    assert set(config["reduced"]) == set(reduced)
    for key, value in row["config"].items():
        assert config[key] == reduced.get(key, value), key
        if key in reduced:
            assert config[key + "_published"] == value
    manifest = load(os.path.join(REPO, "BENCHMARK.json"))
    (entry,) = [c for c in manifest["configs"] if c["name"] == "lfm2-8b-a1b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["why"]) <= 200


def test_parameters_of_the_share_by_hand(lfm2):
    counts = work.param_counts(lfm2)
    # W_in 2048 x 6144, W_out 2048 x 2048, three taps a channel
    assert counts["conv"] == 2048 * 6144 + 2048 * 2048 + 3 * 2048 \
        == 16_783_360
    # W_q, W_o 2048 x 2048, W_k, W_v 2048 x 512, two norms of 64
    assert counts["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512 + 128 \
        == 10_485_888
    assert counts["dense_mlp"] == 3 * 2048 * 7168 == 44_040_192
    assert counts["expert_layer"] == 16 * 11_010_048 + 2048 * 32 + 32
    assert counts["embed_and_head"] == 32768 * 2048        # one leaf
    assert counts["total"] == (
        5 * 16_783_360 + 10_485_888 + 2 * 44_040_192
        + 4 * (16 * 11_010_048 + 65_568) + 67_108_864 + 6 * 4096 + 2048
    ) == 954_523_904


def test_required_flops_a_token_by_hand(lfm2):
    parts = work.forward_flops_per_token(lfm2, 8192)
    assert parts["conv_projections"] == 5 * 2 * 4 * 2048 * 2048
    assert parts["attention_projections"] == 2 * (
        2 * 2048 * 2048 + 2 * 2048 * 512)
    assert parts["attention"] == 32 * 8193 * 2 * 64
    assert parts["dense_mlp"] == 2 * 2 * 44_040_192
    assert parts["router"] == 4 * 2 * 2048 * 32
    # 4 of 32 chosen, 16 held: two experts a token
    assert parts["held_experts"] == 4 * 2 * 11_010_048 * 2
    assert parts["head"] == 2 * 2048 * 32768
    total = sum(parts.values())
    assert 0.708e9 < total < 0.710e9
    assert work.train_flops_per_token(lfm2, 8192) == 3 * total


def test_kernel_work_by_hand(lfm2):
    flash = work.flash_attention_work(lfm2, 1, 8192)
    assert flash["flops"] == 3 * 4 * 64 * (8192 * 8193 // 2) * 32
    assert flash["bytes"] == 6 * (32 + 8) * 8192 * 64 * 2
    conv = work.short_conv_work(lfm2, 8192)
    assert conv["bytes"] == 5 * 11 * 2048 * 8192 * 2 == 1_845_493_760
    gemm = work.expert_gemm_work(lfm2, 8192)
    assert gemm["rows"] == 8192 * 4 * 16 / 32 == 16384      # 1,024 an expert
    assert gemm["flops"] == 4 * 3 * 16384 * 2 * 3 * 2048 * 1792
    row = 2048 + 2 * 1792 + 1792 + 2048
    assert gemm["bytes"] == 4 * 2 * (
        3 * 16 * 3 * 2048 * 1792 + 16384 * (3 * row + 2048 + 1792))
    # compute is the bound at this many rows an expert
    assert gemm["flops"] / 197e12 > 2 * gemm["bytes"] / 819e9


# ----------------------- the readers, on recorded rows -----------------------

def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"_reader_{name}", os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        found = json.load(f)
    observed = {"owners": [owners.Row(*r) for r in found["rows"]],
                "experts_adopted": set(found["experts_adopted"]),
                "work": found["work"], "peaks": found["peaks"],
                "counters": found["counters"]}
    return observed, found["metrics"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_returns_the_recorded_number(recorded, name):
    observed, want = recorded
    assert _reader(name).compute(observed) == pytest.approx(
        want[name], rel=1e-9)


def test_the_recorded_rows_by_scope(recorded):
    """What the recording shows of the readers' shapes: the experts'
    scope takes back one instruction an expert layer from
    `mlp/combine`, nothing else moves, and the roofline shares stay
    under 100."""
    observed, want = recorded
    rows = observed["owners"]
    taken = [r for r in rows if r.name in observed["experts_adopted"]]
    assert len(taken) >= 4
    assert {r.owner.split("/", 1)[1] for r in taken} == {"mlp/combine"}
    by_scope = sum(r.ms for r in rows if r.owner.endswith("/mlp/experts"))
    assert want["top4_expert_gemm_ms"] == pytest.approx(
        by_scope + sum(r.ms for r in taken), rel=1e-9)
    both = sum(r.ms for r in rows
               if r.owner.endswith(("/mlp/dispatch", "/mlp/combine")))
    assert want["top4_dispatch_ms"] == pytest.approx(
        both - sum(r.ms for r in taken), rel=1e-9)
    assert 0 < want["shortconv_gate_hbm_pct"] <= 100
    assert 0 < want["top4_expert_gemm_roofline_pct"] <= 100
    assert want["top4_overflow_assignments"] == 0


ROWS = [
    ("fusion.1", "block0/attn/shortconv", "fwd", "fusion", 1.0),
    ("fusion.2", "block3/attn/shortconv", "bwd", "fusion", 3.0),
    ("fusion.3", "block0/attn/in_proj", "fwd", "fusion", 2.0),
    ("fusion.4", "block5/attn/out_proj", "bwd", "fusion", 0.5),
    ("fusion.5", "block2/attn/qkv", "fwd", "fusion", 4.0),
    ("fusion.6", "block2/attn/qknorm_rope", "bwd", "fusion", 0.25),
    ("fusion.7", "block2/attn/proj", "fwd", "fusion", 1.0),
    # another stack's layer that attends without the norm and the turn
    ("fusion.8", "block1/attn/qkv", "fwd", "fusion", 64.0),
    ("flash_fwd.1", "block2/attn/flash", "fwd", "kernel", 8.0),
    ("fusion.9", "block2/mlp/router", "fwd", "fusion", 0.125),
    ("fusion.10", "block2/mlp/dispatch", "fwd", "fusion", 2.0),
    ("fusion.11", "block2/mlp/experts", "bwd", "fusion", 16.0),
    ("custom-call.1", "block2/mlp/combine", "fwd", "copy", 4.0),
    ("fusion.12", "block2/mlp/combine", "fwd", "fusion", 1.0),
]


def test_the_new_readers_on_rows_by_hand():
    observed = {
        "owners": [owners.Row(*r) for r in ROWS],
        "experts_adopted": {"custom-call.1"},
        "work": {"short_conv": {"bytes": 819e9 * 1e-3},
                 "expert_gemm": {"flops": 197e12 * 5e-3,
                                 "bytes": 819e9 * 2e-3}},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "counters": {"moe_counts": [[10, 30], [20, 20]],
                     "moe_overflow": [0, 0]}}
    assert _reader("shortconv_gate_ms").compute(observed) == 4.0
    assert _reader("shortconv_gate_hbm_pct").compute(observed) == 25.0
    assert _reader("shortconv_proj_ms").compute(observed) == 2.5
    assert _reader("gqa_rope_proj_ms").compute(observed) == 5.25
    assert _reader("top4_expert_gemm_ms").compute(observed) == 20.0
    assert _reader("top4_expert_gemm_roofline_pct").compute(observed) == 25.0
    assert _reader("top4_dispatch_ms").compute(observed) == 3.0
    assert _reader("top4_router_ms").compute(observed) == 0.125
    assert _reader("top4_load_imbalance").compute(observed) == 1.5
    assert _reader("top4_overflow_assignments").compute(observed) == 0


def test_the_new_readers_find_nothing_on_another_program():
    """A program without the scopes or the counters, or a run without
    a trace: None, not 0 and not an exception."""
    other = {"owners": [owners.Row(*r) for r in ROWS if "flash" in r[1]],
             "work": {}, "peaks": None}
    for name in NEW_METRICS:
        assert _reader(name).compute(other) is None, name
        assert _reader(name).compute({"owners": None}) is None, name
