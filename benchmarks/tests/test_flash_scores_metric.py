"""The reader PR 29 added, `flash_scores_per_required`, in
`test_flash_layout_metrics.py`'s style: it reads the program's counter,
finds nothing on a program without one, and reads what the kernels'
static tiling computes.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_flash_scores_metric.py -q`.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.tests.test_owners import reader


@pytest.mark.parametrize("calls,want", [
    ({"scores_computed": 1_048_576, "scores_required": 524_800}, 1.998),
    ({"scores_computed": 655_360, "scores_required": 524_800}, 1.249),
    ({"scores_computed": 4096, "scores_required": 4096}, 1.0),
    ({"scores_computed": 0, "scores_required": 0}, None),
])
def test_flash_scores_per_required_reads_the_programs_counter(calls, want,
                                                              monkeypatch):
    from apex_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "stats",
                        lambda: {"projection_layout": 1, "head_major": 0,
                                 **calls})
    got = reader("flash_scores_per_required").compute({})
    assert got == (want if want is None else pytest.approx(want, abs=1e-3))


@pytest.mark.parametrize("stats", [
    None,                                                   # before PR 27
    lambda: {"projection_layout": 48, "head_major": 0},     # before PR 29
])
def test_flash_scores_per_required_is_none_without_the_counter(stats,
                                                               monkeypatch):
    """A program that counts no scores: nothing read, nothing raised."""
    from apex_tpu.ops import flash_attention

    if stats is None:
        monkeypatch.delattr(flash_attention, "stats")
    else:
        monkeypatch.setattr(flash_attention, "stats", stats)
    assert reader("flash_scores_per_required").compute({}) is None


def _trace_one_layer():
    """One causal call of cell 1's shape a head, traced and not run."""
    from apex_tpu.ops import flash_attention as FA

    FA.reset_stats()
    qkv = jax.ShapeDtypeStruct((1024, 1, 3 * 2 * 64), jnp.bfloat16)
    jax.eval_shape(lambda x: FA.flash_attention_qkv(
        x, 2, causal=True, use_pallas_override=True), qkv)
    return FA.stats()


def test_one_tile_a_block_reads_the_whole_square(monkeypatch):
    """A causal (1024, 1024) block computed in one piece, as the parent
    of PR 29 computed it: 1,048,576 scores for the 524,800 the mask
    keeps, 2.0 to three digits."""
    from apex_tpu.ops import flash_attention as FA

    monkeypatch.setattr(FA, "_pick_tile", lambda bq, backward: bq)
    stats = _trace_one_layer()
    assert (stats["scores_computed"], stats["scores_required"]) == (
        2 * 1_048_576, 2 * 524_800)
    assert reader("flash_scores_per_required").compute({}) == pytest.approx(
        2.0, abs=5e-3)


def test_the_committed_tile_rule_leaves_less_than_the_square():
    """With the rule as committed the same call computes at most 1.5
    times what the mask keeps (the issue's bound for the dense cells)."""
    stats = _trace_one_layer()
    assert stats["scores_required"] == 2 * 524_800
    got = reader("flash_scores_per_required").compute({})
    assert 1.0 <= got <= 1.5
