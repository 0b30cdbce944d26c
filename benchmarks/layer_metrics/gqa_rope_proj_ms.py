"""Device milliseconds a step owned by what stands around the flash
kernels of a QK-normed rotary grouped-query layer: its three
projections (`attn/qkv`), q's and k's per-head norm and rotation on the
way to the kernels (`attn/qknorm_rope`) and the output projection
(`attn/proj`), of the layers that open `attn/qknorm_rope` and of no
other, forward and backward, first device.  None on a program that
opens no such scope."""

import re

from benchmarks.lib import owners


def compute(observed):
    rows = owners.table(observed)
    if rows is None:
        return None
    mine = {m.group(1) for m in (
        re.match(r"(block\d*)/attn/qknorm_rope$", r.owner) for r in rows) if m}
    if not mine:
        return None
    part = re.compile(r"(block\d*)/attn/(qkv|qknorm_rope|proj)$")
    return sum(r.ms for r in rows
               if (m := part.match(r.owner)) and m.group(1) in mine)
