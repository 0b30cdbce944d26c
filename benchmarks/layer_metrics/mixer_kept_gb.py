"""Gigabytes of activations the KDA mixers' checkpoints of one step
were told to keep for the backward besides their normed inputs
(`apex_tpu.models.hybrid_moe.stats()["kept_bytes"]`, counted by the
checkpoint's own policy while the step was differentiated: shape x
itemsize of every activation it answered yes for).  What the saved
recomputation costs in memory, so lower is better at the same
`mixer_recompute_ms`.  0 says no policy was asked: the mixers recompute
everything, or nothing.  None on a program whose model counts no such
thing."""


def compute(observed):
    from apex_tpu.models import hybrid_moe

    if not hasattr(hybrid_moe, "stats"):
        return None
    return hybrid_moe.stats()["kept_bytes"] / 1e9
