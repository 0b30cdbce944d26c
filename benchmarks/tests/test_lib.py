"""The yardstick's arithmetic, held to numbers worked by hand, and the
trace reduction, held to a trace recorded on the chip."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.lib import hlo, peaks, trace, work  # noqa: E402


def config(name):
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           name + ".json")) as f:
        return json.load(f)


# ------------------------------- work.py -------------------------------

def test_sizes_of_both_spellings():
    assert work.model_sizes(config("gpt2-medium")) == {
        "hidden": 1024, "layers": 24, "heads": 16, "ffn": 4096,
        "vocab": 50304, "positions": 1024}
    assert work.model_sizes(config("gpt-1p3b")) == {
        "hidden": 2048, "layers": 24, "heads": 32, "ffn": 8192,
        "vocab": 50304, "positions": 2048}


def test_gpt2_medium_by_hand():
    """h = 1024, f = 4096, L = 24, V = 50304, S = 1024.

    Parameters: embedding 50304 x 1024 = 51,511,296; positions
    1024 x 1024 = 1,048,576; a block 2,048 + 3,148,800 + 1,049,600 +
    2,048 + 4,198,400 + 4,195,328 = 12,596,224, times 24 =
    302,309,376; final LayerNorm 2,048.

    Forward FLOPs a token: a block's GEMMs 2 x (4h^2 + 2hf) =
    25,165,824; its causal attention 2h(S + 1) = 2,099,200; times 24 =
    654,360,576; the head 2hV = 103,022,592; 757,383,168 in all, and
    three times that with the backward."""
    sizes = work.model_sizes(config("gpt2-medium"))
    assert work.gpt_param_count(sizes, 1024) == 354_871_296
    assert work.gpt_train_flops_per_token(sizes, 1024) == 2_272_149_504


def test_flash_work_by_hand():
    """12 x 16 x 1024 x 64 = 12,582,912 elements an array.  Causal
    pairs a head 1024 x 1025 / 2 = 524,800; forward 4 x 64 x 524,800 x
    192 heads = 25,794,969,600; with the backward 77,384,908,800.
    Twelve bf16 arrays: 301,989,888 bytes."""
    assert work.flash_attention_work(12, 16, 1024, 64) == {
        "flops": 77_384_908_800, "bytes": 301_989_888}
    # half the square, not the whole of it
    full = 3 * 2 * 2 * 64 * 1024 * 1024 * 192
    assert work.flash_attention_work(12, 16, 1024, 64)["flops"] \
        == full * 1025 // 2048


@pytest.mark.parametrize("state_bytes,want", [
    (2, 4_968_284_160),     # bf16 state: 6 x 2 + 2 = 14 B an element
    (4, 9_226_813_440)])    # fp32 state: 6 x 4 + 2 = 26 B an element
def test_adam_bytes_by_hand(state_bytes, want):
    assert work.adam_bytes(354_877_440, state_bytes, 2) == want


# ------------------------------- peaks.py -------------------------------

def test_v5e_peaks_and_no_default():
    row = peaks.peaks_for("TPU v5 lite")
    assert (row["bf16_flops_per_s"], row["hbm_bytes_per_s"],
            row["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert "Google Cloud" in row["source"]
    for kind in ("cpu", "TPU v4", "TPU v5p", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            peaks.peaks_for(kind)


# -------------------------------- hlo.py --------------------------------

HLO = '''
  %jvp__.24 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[192,1,1024]{2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="x"}
  %transpose_jvp___.3 = (bf16[192,1024,64]{2,1,0}, bf16[192,1024,64]{2,1,0}, bf16[192,1024,64]{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"
  %local_step.1 = (bf16[2772480,128]{1,0}, bf16[2772480,128]{1,0}, bf16[2772480,128]{1,0}) custom-call(%p), custom_call_target="tpu_custom_call"
  %other = f32[8]{0} custom-call(%p), custom_call_target="Sharding"
  %ag = bf16[8]{0} all-gather(%x), dimensions={0}
  %ars = bf16[8]{0} all-reduce-start(%x)
'''


def test_kernels_are_known_by_what_they_write():
    calls = hlo.custom_calls(HLO)
    assert [name for name, _ in calls] == [
        "jvp__.24", "transpose_jvp___.3", "local_step.1"]
    assert hlo.kernels_writing(calls, 192 * 1024 * 64) == [
        "jvp__.24", "transpose_jvp___.3"]
    assert hlo.kernels_writing(calls, 354_877_440) == ["local_step.1"]
    assert hlo.kernels_writing(calls, 7) == []
    assert hlo.collectives(HLO)["all-gather"] == 1
    assert hlo.collectives(HLO)["all-reduce"] == 1


# ------------------------------- trace.py -------------------------------

def synthetic():
    step = "jit_step(1)"
    return {"devices": {"0": {
        "modules": [[step, 0, 100], [step, 110, 100], [step, 220, 100],
                    [step, 330, 100], ["jit_other(2)", 500, 5]],
        "ops": [["fusion.1", 0, 50], ["fusion.1", 110, 40],
                ["all-gather.1", 150, 30], ["fusion.2", 170, 40],
                ["fusion.1", 220, 100], ["kernel.7", 335, 95]]}},
        "host": [["bench.dispatch", 200, 15], ["bench.wait_loss", 215, 200]]}


def test_reduce_by_hand():
    """The steady window runs from the second execution's start (110)
    to the last's end (430): three steps, 320 ns.  Busy 110-210,
    220-320, 335-430 = 295; idle 25.  The all-gather runs 150-180 and
    a fusion from 170: 20 ns exposed.  Of the idle, 210-215 falls in
    the dispatch span and 215-220 with 320-335 in the wait."""
    r = trace.reduce(synthetic())
    dev = r["devices"]["0"]
    assert r["n_steps"] == 3
    assert r["window_s"] == pytest.approx(320e-9)
    assert r["busy_s"] == pytest.approx(295e-9)
    assert r["idle_pct"] == pytest.approx(100 * 25 / 320)
    assert dev["exposed_collective_s"] == pytest.approx(20e-9)
    assert dev["idle_gaps"] == pytest.approx(
        {"bench.dispatch": 5e-9, "bench.wait_loss": 20e-9, "elsewhere": 0})
    assert dev["ops"]["fusion.1"] == pytest.approx(140e-9)
    observed = {"trace": r, "kernels": {"mine": ["kernel.7"], "none": []}}
    assert trace.kernel_seconds_per_step(observed, "mine") == pytest.approx(
        95e-9 / 3)
    assert trace.kernel_seconds_per_step(observed, "none") is None
    assert trace.kernel_seconds_per_step(observed, "absent") is None
    assert trace.kernel_seconds_per_step(
        {"trace": None, "kernels": {"mine": ["kernel.7"]}}, "mine") is None
    assert trace.top(dev["ops"], 2)[0][0] == "fusion.1"


def test_an_op_event_is_named_by_its_instruction():
    text = ("%fusion.11 = (bf16[8,4]{1,0:T(8,128)(2,1)}, f32[4]{0}) fusion("
            "bf16[8,4]{1,0} %p), kind=kOutput, calls=%fused_computation.16")
    assert trace.short(text) == (
        "fusion.11", "fusion (bf16[8,4]{1,0:T(8,128)(2,1)}, f32[4]{0})")
    assert trace.short("%all-gather.7 = bf16[8]{0} all-gather(bf16[4]{0} "
                       "%x), dimensions={0}")[0] == "all-gather.7"
    assert trace.is_collective("all-gather.7")
    assert trace.is_collective("all-reduce-start.2")
    assert not trace.is_collective("fusion.11", "fusion bf16[8]")
    assert trace.is_collective("ag.3", "all-gather-done bf16[8]")
    grouped = trace.by_label({"f.1": 1.0, "f.2": 2.0, "g": 0.5},
                             {"f.1": "fusion bf16[8]", "f.2": "fusion bf16[8]"})
    assert grouped == {"fusion bf16[8] x2": 3.0, "g x1": 0.5}
    assert trace.top(grouped, 1) == [["fusion bf16[8] x2", 3.0]]


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert trace.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert trace.subtract([[0, 4], [6, 9]], []) == [[0, 4], [6, 9]]
    assert trace.total([[0, 3], [5, 8]]) == 6


def test_too_few_steps_is_an_error():
    events = synthetic()
    events["devices"]["0"]["modules"] = events["devices"]["0"]["modules"][:2]
    with pytest.raises(ValueError, match="too few"):
        trace.reduce(events)


# ------------------- the reduction on a recorded trace -------------------

RECORDED = os.path.join(HERE, "data", "trace_v5e_gpt2-medium_b12s1024")


def test_reduce_on_the_recorded_trace():
    """Four executions of the gpt2-medium step on device 0 of a v5e,
    recorded by PR 24 (events cut from the .xplane.pb by
    `trace.read_xplane`, kernels named by `hlo.kernels_writing` from
    that run's compiled text).  The numbers were computed by this
    reduction when the trace was recorded; a later PR that computes
    busy, idle or kernel time another way fails here."""
    events = trace.load_events(RECORDED + ".json.gz")
    with open(RECORDED + ".expected.json") as f:
        want = json.load(f)
    r = trace.reduce(events)
    first = r["devices"]["0"]
    exact = pytest.approx  # integers of nanoseconds underneath
    assert r["n_steps"] == want["n_steps"] == 3
    assert r["window_s"] == exact(want["window_s"], rel=1e-12)
    assert r["busy_s"] == exact(want["busy_s"], rel=1e-12)
    assert r["idle_pct"] == exact(want["idle_pct"], rel=1e-9)
    assert first["exposed_collective_s"] == want["exposed_collective_s"] == 0
    assert first["idle_gaps"] == exact(want["idle_gaps"], rel=1e-9)
    kernels = want["kernels"]
    assert len(kernels["flash"]) == 48 and len(kernels["adam"]) == 1
    observed = {"trace": r, "kernels": kernels}
    assert trace.kernel_seconds_per_step(observed, "flash") == exact(
        want["flash_s_per_step"], rel=1e-12)
    assert trace.kernel_seconds_per_step(observed, "adam") == exact(
        want["adam_s_per_step"], rel=1e-12)
    top = trace.top(trace.by_label(first["ops"], events["labels"]), 5)
    assert [name for name, _ in top] == [name for name, _ in want["top_ops"]]
    # what a reader should find there: a device-bound step
    assert r["idle_pct"] < 1.0
    assert 0.040 < want["flash_s_per_step"] < 0.060
    assert 0.006 < want["adam_s_per_step"] < 0.010
