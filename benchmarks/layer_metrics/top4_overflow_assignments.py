"""Assignments to held experts that found no row in the grouped
buffers, all expert layers, in a forward of the run's last batch
(`counters["moe_overflow"]`): `moe_overflow_assignments` under this
cell's name.  With half the routed experts held the buffer's bound is
every assignment, so this reads 0 by construction; anything else also
makes the run not `correct`."""

from benchmarks.layer_metrics.moe_overflow_assignments import (  # noqa: F401
    compute,
)
