"""Device milliseconds a step, first device, of the mixers' forward
work that `jax.checkpoint` runs a second time in the backward: the
instructions owned under `block*/attn` whose own `op_name` stands
under `rematted_computation` (`apex_tpu.monitor.scopes.step_rematted`,
from the step's compiled text), joined with the trace as
`lib/owners.py::ms` joins the owners.  A lower bound: an instruction
that states no `op_name` (the compiler's own copies and slices) is not
counted, and a fusion counts by the one `op_name` it carries.  A loop's
own event (`while.N`) is left out, its body's instructions being in the
trace one by one.  None on a program whose `scopes` cannot say what is
rematted, or that has no trace."""

import re

from benchmarks.lib import owners


def compute(observed):
    rows = owners.table(observed)
    if rows is None:
        return None
    from apex_tpu.monitor import scopes

    if not hasattr(scopes, "step_rematted"):
        return None
    again = scopes.step_rematted()
    return sum(r.ms for r in rows
               if r.name in again and re.match(r"block\d*/attn", r.owner)
               and not r.name.startswith("while"))
