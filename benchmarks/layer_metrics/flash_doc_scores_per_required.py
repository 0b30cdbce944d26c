"""Score elements the flash kernels of the step compute for each one
the document mask and the causal mask keep: the counter
`flash_scores_per_required` reads (`apex_tpu.ops.flash_attention.
stats()`, taken by the job while the step was traced: `scores_computed`
and `scores_required`, the latter the pairs a causal mask alone keeps)
with the required pairs cut to the share of them that lie inside one
document (`doc_pairs_share`, from the lengths of the documents the
job's ring holds).  1.1 would be kernels that skip what a boundary
empties; kernels that do not read the causal ratio over the share.
None where the job counted none."""


def compute(observed):
    counters = observed.get("counters", {})
    required = counters.get("flash_scores_required")
    share = counters.get("doc_pairs_share")
    if not (required and share):
        return None
    return counters["flash_scores_computed"] / (required * share)
