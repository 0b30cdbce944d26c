"""Device milliseconds a step owned by the scope `block0/attn/segments`:
what knowing the documents of a packed row costs a step (the EOD
compare, the running count of documents, the first-token mask and the
taps' masks, made once from the step's tokens), first device.  What the
mixers then do with them counts under their own scopes.  None on a
program that opens no such scope."""

from benchmarks.lib import owners


def compute(observed):
    rows = owners.table(observed)
    if rows is None:
        return None
    mine = [r.ms for r in rows if r.owner.endswith("/attn/segments")]
    return sum(mine) if mine else None
