"""Documents in a step's rows, the mean over the job's ring of batches,
a cut last document counted as one: the traffic's own number, so that a
reader of the ledger sees when the generator moved.  None where the job
packs no documents."""


def compute(observed):
    return observed.get("counters", {}).get("docs_per_step")
