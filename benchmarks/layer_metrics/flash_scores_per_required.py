"""Score elements the flash forward kernels of this run's traced calls
compute for each one a causal mask keeps
(`apex_tpu.ops.flash_attention.stats()`: scores_computed /
scores_required, counted while tracing from each call's static blocks
and compute tiles).  One block a head computes the whole square, 2.0 at
a long sequence; blocks or tiles above the diagonal that never run
bring it towards 1.0, and a call without a mask reads 1.0.  None on a
program that has no such counter."""


def compute(observed):
    from apex_tpu.ops import flash_attention

    stats = getattr(flash_attention, "stats", None)
    calls = stats() if stats is not None else {}
    required = calls.get("scores_required")
    return calls["scores_computed"] / required if required else None
