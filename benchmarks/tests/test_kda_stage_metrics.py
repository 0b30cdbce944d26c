"""The two readers PR 35 added, in `test_kda_kernel_metric.py`'s
style: `kda_stage_kernel_pct` reads the program's counter and finds
nothing on a program without the op; `kda_conv_ms` reads one scope of
the owners' table.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_kda_stage_metrics.py -q`.
"""

import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks.tests.test_owners import reader


@pytest.mark.parametrize("calls,want", [
    ({"calls": 9, "kernel_calls": 9}, 100.0),
    ({"calls": 4, "kernel_calls": 1}, 25.0),
    ({"calls": 3, "kernel_calls": 0}, 0.0),
    ({"calls": 0, "kernel_calls": 0}, None),      # no call traced
])
def test_kda_stage_kernel_pct_reads_the_programs_counter(calls, want,
                                                         monkeypatch):
    from apex_tpu.ops import conv_stage

    monkeypatch.setattr(conv_stage, "stats", lambda: dict(calls))
    assert reader("kda_stage_kernel_pct").compute({}) == want


def test_it_finds_nothing_on_a_program_without_the_op(monkeypatch):
    """The parent commit has no `apex_tpu.ops.conv_stage`."""
    import apex_tpu.ops

    monkeypatch.delattr(apex_tpu.ops, "conv_stage", raising=False)
    monkeypatch.setitem(sys.modules, "apex_tpu.ops.conv_stage", None)
    assert reader("kda_stage_kernel_pct").compute({}) is None


@pytest.mark.parametrize("width,want", [(128, 100.0), (64, 0.0)])
def test_it_follows_the_path_a_traced_call_takes(width, want):
    """A 128-wide head with the kernels asked for counts; a 64-wide one
    takes the `jax.numpy` body whatever is asked."""
    from apex_tpu.ops import conv_stage

    conv_stage.reset_stats()
    x = jax.ShapeDtypeStruct((1, 32, 2 * width), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 2 * width), jnp.bfloat16)
    jax.eval_shape(lambda x, w: conv_stage.stage_conv_heads(
        (x,), (w,), 2, (1.0,), use_pallas_override=True), x, w)
    assert reader("kda_stage_kernel_pct").compute({}) == want
    conv_stage.reset_stats()


def test_kda_conv_ms_is_the_conv_scope_alone():
    from benchmarks.lib.owners import Row

    rows = [Row("conv_stage.3", "block1/attn/conv", "fwd", "kernel", 1.5),
            Row("conv_unstage.7", "block2/attn/conv", "bwd", "kernel", 2.25),
            Row("fusion.12", "block1/attn/onorm", "bwd", "fusion", 7.0),
            Row("kda_locals_fwd.1", "block1/attn/scan", "fwd", "kernel", 11.0)]
    conv = reader("kda_conv_ms").compute
    assert conv({"owners": rows}) == 3.75
    assert conv({"owners": rows[2:]}) is None      # no such scope
    assert conv({"owners": None}) is None          # no trace
