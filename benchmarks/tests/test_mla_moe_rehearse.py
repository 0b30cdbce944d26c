"""The `mla_moe_train` job end to end on the CPU, through `run.py
--rehearse`, on a tiny configuration with a manifest this file writes
itself; a negative control for `correct`; and `lib/work_mla_moe.py`
against counts done by hand.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_mla_moe_rehearse.py -q`.  No number printed here
is a measurement.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks.lib import work, work_mla_moe  # noqa: E402
from benchmarks.tests.test_rehearse import (  # noqa: E402
    RESULT_KEYS, dump, last_line, load, run_cell)

CELL = "wee-moe.train"
NEW_METRICS = ("mla_proj_ms", "moe_router_ms", "moe_dispatch_ms",
               "moe_expert_gemm_ms", "moe_expert_gemm_roofline_pct",
               "mtp_ms", "moe_load_imbalance", "moe_overflow_assignments")
# the family's keys at toy sizes: 1 dense + 2 expert layers and the MTP
# module, experts [4, 12) of 16, top 3; weights large enough that a toy
# this narrow is no flat function of them
TINY = {
    "name": "wee-moe", "source": "none: a toy for the CPU rehearsal",
    "family": "joyai_llm_flash", "reference": "joyai_llm_flash",
    "hidden_size": 32, "num_attention_heads": 2, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "intermediate_size": 48, "moe_intermediate_size": 8,
    "n_routed_experts": 8, "n_routed_experts_published": 16,
    "experts_first": 4, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3,
    "initializer_range": 0.06, "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "vocab_size": 64,
    "max_position_embeddings": 64, "reduced": [],
}
WORKLOAD = {
    "name": CELL, "config": "wee-moe", "chips": 1, "job": "mla_moe_train",
    "params": {"batch": 2, "seq": 32, "tensor_parallel": 1,
               "sequence_parallel": False, "state_dtype": "bfloat16",
               "lr": 1e-3},
    "why": "CPU rehearsal",
}


@pytest.fixture
def bench(tmp_path):
    """write(config=TINY) -> the path of a manifest of the one tiny
    cell, with the real manifest's metrics."""
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    manifest = dict(
        real, paths=["bm"],
        configs=[{"name": "wee-moe", "source": "none",
                  "file": "bm/configs/wee-moe.json", "reduced": [],
                  "why": "CPU rehearsal"}],
        workloads=[{"name": CELL, "config": "wee-moe", "traffic": "train",
                    "chips": 1, "why": "CPU rehearsal"}],
        per_layer=[dict(m, workloads=[CELL] if m["name"] in NEW_METRICS
                        else []) if "workloads" in m else m
                   for m in real["per_layer"]])
    root = str(tmp_path)

    def write(config=TINY):
        dump(config, root, "bm", "configs", "wee-moe.json")
        dump(WORKLOAD, root, "bm", "workloads", CELL + ".json")
        return dump(manifest, root, "BENCHMARK.json")

    write.root = root
    return write


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_end_to_end(bench, trace):
    proc = run_cell(bench(), CELL, trace=trace, seed=2 ** 31 + 11)
    line = last_line(proc)
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 16
    assert line["device"]["count"] == 1
    if not trace:
        assert set(line["metrics"]) == {
            "train_tokens_per_s", "loss_after_16_steps", "setup_s"}
        assert all(m["value"] is None for m in line["metrics"].values())
        return
    metrics = line["metrics"]
    # the counters are numbers; nothing read from a device trace is
    assert metrics["moe_overflow_assignments"] == {"value": 0,
                                                   "unit": "count"}
    assert metrics["moe_load_imbalance"]["value"] >= 1.0
    assert metrics["steady_recompiles"]["value"] == 0
    for name in ("mla_proj_ms", "moe_expert_gemm_ms",
                 "moe_expert_gemm_roofline_pct", "mtp_ms"):
        assert metrics.get(name, {"value": None})["value"] is None
    # both heads were held to the reference, and the routers counted
    assert '"mtp": {"system_mean"' in proc.stdout
    assert '"moe_counts"' in proc.stdout


def test_one_perturbed_expert_makes_the_run_incorrect(bench):
    """The negative control: against a reference in which one held
    expert of one layer has other weights, the same run is not
    `correct`."""
    path = os.path.join(bench.root, "bm", "reference", "one_expert_off.py")
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as f:
        f.write(
            "from benchmarks.reference import joyai_llm_flash as ref\n\n\n"
            "def token_losses(params, *args, **kw):\n"
            "    params = dict(params)\n"
            "    block = dict(params['block1'])\n"
            "    mlp = dict(block['mlp'])\n"
            "    down = mlp['experts_down']\n"
            "    mlp['experts_down'] = down.at[3].set(down[3] * 3.0)\n"
            "    block['mlp'] = mlp\n"
            "    params['block1'] = block\n"
            "    return ref.token_losses(params, *args, **kw)\n")
    proc = run_cell(bench(dict(TINY, reference="one_expert_off")), CELL)
    line = last_line(proc)
    assert line["correct"] is False
    assert line["failed"] == 0   # the steps themselves were fine
    assert '"agrees": false' in proc.stdout


# ----------------------- lib/work_mla_moe.py by hand -----------------------

@pytest.fixture(scope="module")
def joyai():
    return work_mla_moe.sizes(load(os.path.join(
        BENCH, "configs", "joyai-llm-flash.json")))


def test_parameters_of_the_share_by_hand(joyai):
    counts = work_mla_moe.param_counts(joyai)
    # W_qa + its norm, W_qb, W_kva, its norm, W_kvb, W_o
    assert counts["mla"] == (2048 * 1536 + 1536 + 1536 * 32 * 192
                             + 2048 * 576 + 512 + 512 * 32 * 256
                             + 4096 * 2048) == 26_347_520
    assert counts["dense_mlp"] == 3 * 2048 * 7168
    assert counts["held_experts_a_layer"] == 16 * 3 * 2048 * 768
    assert counts["expert_layer"] == (2048 * 256 + 256 + 17 * 3 * 2048 * 768)
    assert counts["embed_and_head"] == 2 * 16256 * 2048
    # 6 blocks (5 + the MTP module's), 1 dense, 5 expert layers
    assert counts["total"] == (
        6 * (26_347_520 + 4096) + 44_040_192 + 5 * 80_740_608
        + 2 * 2048 * 2048 + 3 * 2048 + 66_584_576 + 2048) == 680_834_304


def test_required_flops_a_token_by_hand(joyai):
    parts = work_mla_moe.forward_flops_per_token(joyai, 4096)
    assert parts["mla_projections"] == 6 * 2 * (26_347_520 - 1536 - 512)
    assert parts["attention"] == 6 * 32 * 4097 * (192 + 128)
    assert parts["dense_mlp"] == 2 * 3 * 2048 * 7168
    assert parts["router"] == 5 * 2 * 2048 * 256
    assert parts["shared_expert"] == 5 * 2 * 3 * 2048 * 768
    # 8 of 256 chosen, 16 held: half an expert a token
    assert parts["held_experts"] == 5 * 0.5 * 2 * 3 * 2048 * 768
    assert parts["heads"] == 2 * 2 * 2048 * 16256
    assert parts["mtp_projection"] == 2 * 4096 * 2048
    total = sum(parts.values())
    assert 881e6 < total < 883e6
    assert work_mla_moe.train_flops_per_token(joyai, 4096) == 3 * total


def test_flash_work_at_two_widths_by_hand():
    got = work_mla_moe.flash_attention_work(2, 32, 4096, 192, 128)
    pairs = 4096 * 4097 // 2
    assert got["flops"] == 3 * 2 * (192 + 128) * pairs * 64
    assert got["bytes"] == 6 * (192 + 128) * 64 * 4096 * 2
    # one width: the dense cells' count
    assert work_mla_moe.flash_attention_work(
        12, 16, 1024, 64, 64) == work.flash_attention_work(12, 16, 1024, 64)


def test_expert_gemm_work_by_hand(joyai):
    got = work_mla_moe.expert_gemm_work(joyai, 8192)
    assert got["rows"] == 8192 * 8 * 16 / 256 == 4096
    assert got["flops"] == 5 * 3 * 4096 * 2 * 3 * 2048 * 768
    weights = 16 * 3 * 2048 * 768
    forward_row = 2048 + 2 * 768 + 768 + 2048
    assert got["bytes"] == 5 * 2 * (
        3 * weights + 4096 * (3 * forward_row + 2048 + 768))


# ------------------------- the readers, on rows by hand -------------------------

def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"_reader_{name}", os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_new_readers_on_an_owner_table_by_hand():
    """One expert block (block1) and the MTP module's (block2): the
    grouped-matmul kernels are found by name wherever the owner table
    filed them under a block's mlp."""
    from benchmarks.lib.owners import Row

    rows = [
        Row("ragged-dot-none.3", "block1/mlp/combine", "fwd", "copy", 1.0),
        Row("ragged-dot-none.4", "block1/mlp/experts", "bwd", "copy", 2.0),
        Row("fusion.1", "block1/mlp/experts", "fwd", "fusion", 0.5),
        Row("fusion.2", "block1/mlp/combine", "fwd", "fusion", 4.0),
        Row("sort.1", "block1/mlp/dispatch", "fwd", "copy", 8.0),
        Row("fusion.3", "block1/mlp/router", "fwd", "fusion", 16.0),
        Row("fusion.4", "block1/attn/q_b", "bwd", "fusion", 32.0),
        Row("fusion.5", "block1/attn/rope", "fwd", "fusion", 64.0),
        Row("flash_fwd.1", "block1/attn/flash", "fwd", "kernel", 128.0),
        Row("fusion.6", "block2/mlp/shared", "fwd", "fusion", 256.0),
        Row("fusion.7", "mtp/proj", "fwd", "fusion", 512.0),
        Row("fusion.8", "mtp", "bwd", "fusion", 1024.0),
        Row("fusion.9", "head", "fwd", "fusion", 2048.0),
    ]
    observed = {
        "owners": rows, "counters": {"mtp_block": 2, "moe_counts": [
            [10, 30, 20, 20], [5, 5, 5, 5]], "moe_overflow": [0, 3]},
        "work": {"expert_gemm": {"flops": 197e12 * 1e-3, "bytes": 1.0}},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    want = {"mla_proj_ms": 96.0, "moe_router_ms": 16.0,
            "moe_dispatch_ms": 12.0, "moe_expert_gemm_ms": 3.5,
            "mtp_ms": 256.0 + 512.0 + 1024.0,
            # 1 ms of required work over 3.5 ms
            "moe_expert_gemm_roofline_pct": 100.0 / 3.5,
            "moe_load_imbalance": 1.5, "moe_overflow_assignments": 3}
    for name, value in want.items():
        assert _reader(name).compute(observed) == pytest.approx(value), name
    # a program without these spans and counters: nothing, and no error
    for name in want:
        assert _reader(name).compute(
            {"trace": None, "counters": {}, "spans": {}}) is None
