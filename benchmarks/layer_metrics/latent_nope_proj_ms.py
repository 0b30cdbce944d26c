"""Device milliseconds a step owned by the projections of latent
attention without positions and without a query compression
(`block*/attn/q`, `kv_a` with its norm, `kv_b`, `stage`: q's and k's
head-major operands with nothing turned; and `attn/proj` of the layers
that have them, not the KDA layers' own), forward and backward, first
device.  None on a program that opens no `attn/stage`."""

import re

from benchmarks.lib import owners


def compute(observed):
    rows = owners.table(observed)
    if rows is None:
        return None
    latent = {m.group(1) for m in (
        re.match(r"(block\d*)/attn/stage$", r.owner) for r in rows) if m}
    if not latent:
        return None
    mine = re.compile(r"(block\d*)/attn/(q|kv_a|kv_b|stage|proj)$")
    return sum(r.ms for r in rows
               if (m := mine.match(r.owner)) and m.group(1) in latent)
