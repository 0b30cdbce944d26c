"""The reader PR 33 added, `kda_kernel_pct`, in
`test_flash_scores_metric.py`'s style: it reads the program's counter
and finds nothing on a program without one.

Run by hand, as the rest of this directory: `python -m pytest
benchmarks/tests/test_kda_kernel_metric.py -q`.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.tests.test_owners import reader


@pytest.mark.parametrize("calls,want", [
    ({"calls": 3, "kernel_calls": 3}, 100.0),
    ({"calls": 4, "kernel_calls": 3}, 75.0),
    ({"calls": 3, "kernel_calls": 0}, 0.0),
    ({"calls": 0, "kernel_calls": 0}, None),      # no call traced
    ({"calls": 3}, None),                         # the parent: no counter
])
def test_kda_kernel_pct_reads_the_programs_counter(calls, want, monkeypatch):
    from apex_tpu.ops import delta_rule

    monkeypatch.setattr(delta_rule, "stats", lambda: {
        "chunk": 64, "saved_state_bytes": 0, **calls})
    assert reader("kda_kernel_pct").compute({}) == want


@pytest.mark.parametrize("width,want", [(128, 100.0), (16, 0.0)])
def test_it_follows_the_path_a_traced_call_takes(width, want):
    """A 128-wide head with the kernels asked for counts; a 16-wide one
    takes the compiled stage whatever is asked."""
    from apex_tpu.ops import delta_rule

    delta_rule.reset_stats()
    x = jax.ShapeDtypeStruct((1, 2, 128, width), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 2, 128, width), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 2, 128), jnp.float32)
    jax.eval_shape(lambda *a: delta_rule.gated_delta_rule(
        *a, chunk=64, use_pallas_override=True), x, x, x, g, beta)
    assert reader("kda_kernel_pct").compute({}) == want
    delta_rule.reset_stats()
