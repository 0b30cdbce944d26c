"""The `hybrid_moe_train` job end to end on the CPU, through `run.py
--rehearse`, on a tiny configuration with a manifest this file writes
itself; a negative control for `correct`; `lib/work_hybrid_moe.py`
against counts done by hand; the four readers on rows by hand.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_hybrid_moe_rehearse.py -q`.  No number printed
here is a measurement.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks.lib import owners, work_hybrid_moe  # noqa: E402
from benchmarks.tests.test_rehearse import (  # noqa: E402
    RESULT_KEYS, dump, last_line, load, run_cell)

CELL = "wee-hybrid.train"
NEW_METRICS = ("kda_scan_ms", "kda_scan_roofline_pct", "kda_glue_ms",
               "kda_saved_state_gb")
# the model's keys at toy sizes: one period (layer 0 attends, 4 query
# heads on 2 kv heads; layers 1-3 KDA, 3 heads), experts [4, 12) of 20,
# top 3; weights large enough that a toy this narrow is no flat function
# of them
TINY = {
    "name": "wee-hybrid", "source": "none: a toy for the CPU rehearsal",
    "family": "solar_open2", "reference": "solar_open2",
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 3, "num_kv_heads": None},
    "gqa_layers": [0, 4, 8], "use_rope": False, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "moe_intermediate_size": 8, "n_routed_experts": 8,
    "n_routed_experts_published": 20, "experts_first": 4,
    "num_experts_per_tok": 3, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_hidden_layers": 4,
    "first_k_dense_replace": 0, "initializer_range": 0.06,
    "rms_norm_eps": 1e-5, "vocab_size": 64, "max_position_embeddings": 64,
    "reduced": [],
}
WORKLOAD = {
    "name": CELL, "config": "wee-hybrid", "chips": 1,
    "job": "hybrid_moe_train",
    "params": {"batch": 2, "seq": 32, "tensor_parallel": 1,
               "sequence_parallel": False, "state_dtype": "bfloat16",
               "lr": 1e-3},
    "why": "CPU rehearsal",
}


@pytest.fixture
def bench(tmp_path):
    """write(config=TINY, **params) -> the path of a manifest of the one
    tiny cell, with the real manifest's metrics."""
    real = load(os.path.join(REPO, "BENCHMARK.json"))
    manifest = dict(
        real, paths=["bm"],
        configs=[{"name": "wee-hybrid", "source": "none",
                  "file": "bm/configs/wee-hybrid.json", "reduced": [],
                  "why": "CPU rehearsal"}],
        workloads=[{"name": CELL, "config": "wee-hybrid", "traffic": "train",
                    "chips": 1, "why": "CPU rehearsal"}],
        per_layer=[dict(m, workloads=[CELL] if m["name"] in NEW_METRICS
                        else []) if "workloads" in m else m
                   for m in real["per_layer"]])
    root = str(tmp_path)

    def write(config=TINY, **params):
        dump(config, root, "bm", "configs", "wee-hybrid.json")
        dump(dict(WORKLOAD, params=dict(WORKLOAD["params"], **params)),
             root, "bm", "workloads", CELL + ".json")
        return dump(manifest, root, "BENCHMARK.json")

    write.root = root
    return write


@pytest.mark.parametrize("trace,recompute", [(0, False), (1, False),
                                             (1, True)])
def test_tiny_cell_end_to_end(bench, trace, recompute):
    proc = run_cell(bench(recompute_mixers=recompute), CELL, trace=trace,
                    seed=2 ** 31 + 11)
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
    # the counters are numbers; nothing read from a device trace is.
    # 3 KDA layers x 2 sequences x 3 heads x 1 chunk of 32 x (8, 8) fp32
    assert metrics["kda_saved_state_gb"] == {
        "value": 3 * 2 * 3 * 1 * 8 * 8 * 4 / 1e9, "unit": "GB"}
    assert metrics["steady_recompiles"]["value"] == 0
    for name in ("kda_scan_ms", "kda_scan_roofline_pct", "kda_glue_ms",
                 "flash_attn_ms", "unowned_ms"):
        assert metrics.get(name, {"value": None})["value"] is None
    # the old cells' listed readers are not this cell's
    assert not {"moe_router_ms", "mtp_ms", "mla_proj_ms"} & set(metrics)
    assert '"rms_gap"' in proc.stdout and '"moe_counts"' in proc.stdout
    assert '"phase": "scan_check"' in proc.stdout
    assert '"delta_rule": {"calls": 3, "chunk": 32' in proc.stdout


NO_DECAY = {
    # the whole network's check: KDA layers that never decay their state
    # (`a_log` far below any A)
    "losses": (
        "scan_outputs = ref.scan_outputs\n\n\n"
        "def token_losses(params, *args, **kw):\n"
        "    params = dict(params)\n"
        "    for i in (1, 2, 3):\n"
        "        block = dict(params[f'block{i}'])\n"
        "        attn = dict(block['attn'])\n"
        "        attn['a_log'] = attn['a_log'] - 30.0\n"
        "        block['attn'] = attn\n"
        "        params[f'block{i}'] = block\n"
        "    return ref.token_losses(params, *args, **kw)\n"),
    # the op's own check, whose decays are slow already: a recurrence
    # that forgets thirty times as fast
    "scan": (
        "token_losses = ref.token_losses\n\n\n"
        "def scan_outputs(q, k, v, g, beta, **kw):\n"
        "    return ref.scan_outputs(q, k, v, 30.0 * g, beta, **kw)\n"),
}


@pytest.mark.parametrize("which", sorted(NO_DECAY))
def test_a_scan_with_another_decay_makes_the_run_incorrect(bench, which):
    """The negative controls: against a reference whose delta rule
    decays its state otherwise, in its token losses or in the
    recurrence the op alone is held to, the same run is not `correct`,
    by that check and not by the other."""
    path = os.path.join(bench.root, "bm", "reference", "no_decay.py")
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as f:
        f.write("from benchmarks.reference import solar_open2 as ref\n\n"
                + NO_DECAY[which])
    proc = run_cell(bench(dict(TINY, reference="no_decay")), CELL)
    line = last_line(proc)
    assert line["correct"] is False
    assert line["failed"] == 0   # the steps themselves were fine
    records = [load_line for load_line in proc.stdout.splitlines()
               if '"agrees"' in load_line]
    failed = {"losses": '"phase": "reference"', "scan": '"phase": "scan_check"'}
    assert all(('"agrees": false' in r) == (failed[which] in r)
               for r in records if '"phase": "incorrect"' not in r), records


# --------------------- lib/work_hybrid_moe.py by hand ---------------------

@pytest.fixture(scope="module")
def solar():
    return work_hybrid_moe.sizes(load(os.path.join(
        BENCH, "configs", "solar-open2-250b.json")))


def test_the_share_by_hand(solar):
    assert (solar["layers"], solar["attention"], solar["kda"]) == (4, 1, 3)
    assert solar["attends"] == (0,)
    assert (solar["held"], solar["published"], solar["top_k"]) == (8, 320, 8)


def test_parameters_of_the_share_by_hand(solar):
    counts = work_hybrid_moe.param_counts(solar)
    # W_q, W_gate, W_o at 4096 x 8192; W_k, W_v at 4096 x 1024
    assert counts["attention"] == 3 * 4096 * 8192 + 2 * 4096 * 1024
    # four 4096 x 8192, two rank-128 pairs, W_beta; three 4-tap
    # convolutions, A_h, dt_bias, the output norm
    assert counts["kda"] == (
        4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
        + 3 * 4 * 8192 + 64 + 8192 + 128) == 137_732_288
    assert counts["held_experts_a_layer"] == 8 * 3 * 4096 * 1280
    assert counts["expert_layer"] == 4096 * 320 + 320 + 9 * 3 * 4096 * 1280
    assert counts["embed_and_head"] == 2 * 24576 * 4096
    assert counts["total"] == (
        109_051_904 + 3 * 137_732_288 + 4 * (8192 + 142_868_800)
        + 201_326_592 + 4096) == 1_295_087_424


def test_required_flops_a_token_by_hand(solar):
    parts = work_hybrid_moe.forward_flops_per_token(solar, 4096)
    assert parts["attention_projections"] == 2 * 109_051_904
    assert parts["attention"] == 64 * 4097 * 2 * 128
    assert parts["kda_projections"] == 3 * 2 * (
        4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64)
    # the recurrence: decay, k's read, the rank-one update, q's read
    assert parts["scan"] == 3 * 64 * 7 * 128 * 128
    assert parts["router"] == 4 * 2 * 4096 * 320
    assert parts["shared_expert"] == 4 * 2 * 3 * 4096 * 1280
    # 8 of 320 chosen, 8 held: a fifth of an expert a token
    assert parts["held_experts"] == 4 * 2 * 3 * 4096 * 1280 * 8 * 8 / 320
    assert parts["head"] == 2 * 4096 * 24576
    total = sum(parts.values())
    assert 1.49e9 < total < 1.50e9
    assert work_hybrid_moe.train_flops_per_token(solar, 4096) == 3 * total


def test_grouped_query_flash_work_by_hand():
    got = work_hybrid_moe.flash_attention_work(1, 64, 8, 4096, 128)
    pairs = 4096 * 4097 // 2
    assert got["flops"] == 3 * 2 * 2 * 128 * pairs * 64
    # q, o, do, dq, and q, o again: six arrays of 64 heads; k, v, dk, dv
    # and k, v again: six of 8
    assert got["bytes"] == 6 * (64 + 8) * 4096 * 128 * 2
    # a kv head a query head is the dense cells' count
    from benchmarks.lib import work
    assert work_hybrid_moe.flash_attention_work(
        12, 16, 16, 1024, 64) == work.flash_attention_work(12, 16, 1024, 64)


def test_scan_work_by_hand(solar):
    got = work_hybrid_moe.scan_work(solar, 1, 4096)
    assert got["flops"] == 3 * 3 * 4096 * 64 * 7 * 128 * 128
    inputs = 3 * 128 * 2 + 128 * 4 + 4      # q, k, v; g and beta in fp32
    assert got["bytes"] == 3 * 4096 * 64 * (
        (inputs + 256) + (inputs + 256) + inputs)
    # the bytes decide on a v5e, by far
    assert got["bytes"] / 819e9 > 2 * got["flops"] / 197e12


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
    ("fusion.1", "block1/attn/scan", "fwd", "fusion", 2.0),
    ("fusion.7", "block2/attn/scan", "bwd", "fusion", 3.0),
    # a loop's own event spans its body's instructions: left out
    ("while.3", "block2/attn/scan", "bwd", "copy", 10.0),
    ("fusion.2", "block1/attn/conv", "fwd", "fusion", 0.5),
    ("fusion.3", "block3/attn/decay", "bwd", "fusion", 0.25),
    ("fusion.4", "block3/attn/onorm", "bwd", "fusion", 0.125),
    ("fusion.5", "block1/attn/qkv", "fwd", "fusion", 7.0),
    ("flash_fwd.1", "block0/attn/flash", "fwd", "kernel", 4.0),
    ("fusion.6", "block0/attn/gate", "fwd", "fusion", 1.0),
]


def test_the_scan_readers_on_rows_by_hand():
    observed = _observed(
        ROWS, work={"scan": {"flops": 197e12 * 1e-3, "bytes": 819e9 * 2e-3}},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert _reader("kda_scan_ms").compute(observed) == 5.0
    assert _reader("kda_glue_ms").compute(observed) == 0.875
    # the bytes' 2 ms over the 5 ms the scope took
    assert _reader("kda_scan_roofline_pct").compute(observed) == 40.0
    assert _reader("attn_sublayer_ms").compute(observed) == 27.875


def test_the_readers_find_nothing_on_another_program():
    """A program without the scopes, or a run without a trace: None,
    not 0 and not an exception."""
    other = _observed([r for r in ROWS if "flash" in r[1]], work={},
                      peaks=None)
    for name in ("kda_scan_ms", "kda_glue_ms", "kda_scan_roofline_pct",
                 "kda_saved_state_gb"):
        assert _reader(name).compute(other) is None
        assert _reader(name).compute({"owners": None}) is None
    assert _reader("kda_saved_state_gb").compute(
        {"counters": {"kda_saved_state_bytes": 805306368}}) == 0.805306368
