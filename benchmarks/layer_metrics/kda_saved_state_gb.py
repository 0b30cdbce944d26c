"""Gigabytes of chunk-start states the delta rule's forwards keep for
their backwards in one step, every KDA layer
(`apex_tpu.ops.delta_rule.stats()["saved_state_bytes"]`, counted while
the step was traced: sequence / chunk states of (d_k, d_v) float32 a
head and layer).  None where the job counted none."""


def compute(observed):
    saved = observed.get("counters", {}).get("kda_saved_state_bytes")
    return saved / 1e9 if saved else None
