"""Device milliseconds a step in the held experts' grouped GEMMs, all
expert layers, first device, found by scope and by no instruction's
name: what the owner table files under `block*/mlp/experts` (the
grouped products of gate and up and of down, their input and weight
gradients, SiLU and product, the zeroing of the rows past the last
group), and besides the instructions that state no scope themselves,
were filed under another scope of the same block's `mlp` through their
users, and read what `mlp/experts` made.  The compiler's grouped-matmul
kernels drop their `op_name`, so the owner table files the forward
down-projection, whose one user is the weighted scatter-add, under
`mlp/combine`: its operand says whose it is.  None on a program that
keeps no owner table."""

import re

from benchmarks.lib import owners

EXPERTS = re.compile(r"(block\d*)/mlp/experts$")
OTHER_MLP = re.compile(r"(block\d*)/mlp(/|$)")


def adopted(observed):
    """The instructions the experts' scope takes back: {name}."""
    if "experts_adopted" in observed:
        return observed["experts_adopted"]
    from apex_tpu.monitor import scopes
    from apex_tpu.monitor.comms.hlo import parse_module

    text = scopes.step_text()
    owner = {name: o for name, (o, _, _) in scopes.owners(text).items()}
    found = set()
    for comp in parse_module(text):
        for i in comp.instructions:
            mine = OTHER_MLP.match(owner.get(i.name, ""))
            if (mine is None or EXPERTS.match(owner[i.name])
                    or scopes.owner_of(i.op_name)[0] is not None):
                continue
            if any((m := EXPERTS.match(owner.get(p, "")))
                   and m.group(1) == mine.group(1) for p in i.operand_names):
                found.add(i.name)
    observed["experts_adopted"] = found
    return found


def compute(observed):
    rows = owners.table(observed)
    if rows is None:
        return None
    if not any(EXPERTS.match(r.owner) for r in rows):
        return None
    taken = adopted(observed)
    return sum(r.ms for r in rows
               if EXPERTS.match(r.owner) or r.name in taken)
