"""apex_tpu.moe — expert-parallel Mixture-of-Experts (ISSUE 13).

The "harsher second customer" of ROADMAP item 5: a top-k router with
fp32 gates and capacity-factor token dropping (router.py), dense
dispatch/combine whose cross-expert exchange is ONE all_to_all over
the `ep` mesh axis each way (dispatch.py — the densify-before-the-
collective rule of arXiv 1905.04035), and `MoEMLP` (layer.py), the
drop-in for a transformer block's MLP that `models/moe_gpt.py` trains
under the unmodified `ddp.make_train_step` with the existing ZeRO
machinery (flat master state sharded over the combined ("dp", "ep")
axes).

Host-side telemetry bridge: `MoERecorder` holds the newest step's
MoE aux scalars so `MetricsLogger(moe=recorder)` stamps the schema-v9
`moe_*` fields into every record — the same attachment pattern as the
serve/fleet planes, zero added device syncs (the step already returns
the aux pytree; `update` is fed the host copy the logger fetch pays
for anyway).
"""

from __future__ import annotations

from apex_tpu.moe.layer import (  # noqa: F401
    HeldExpertsMLP,
    HeldExpertsStats,
    MoEAux,
    MoEMLP,
    mean_aux,
)
from apex_tpu.moe.router import (  # noqa: F401
    RouterOutput,
    SigmoidRouterOutput,
    capacity_destinations,
    expert_capacity,
    sigmoid_topk_gates,
    topk_gates,
    topk_gates_blocked,
    topk_gates_dense,
)

__all__ = [
    "HeldExpertsMLP", "HeldExpertsStats",
    "MoEAux", "MoEMLP", "mean_aux", "MoERecorder",
    "RouterOutput", "SigmoidRouterOutput", "capacity_destinations",
    "expert_capacity", "sigmoid_topk_gates",
    "topk_gates", "topk_gates_blocked", "topk_gates_dense",
]


class MoERecorder:
    """Host-side holder of the newest MoE step aux for the logger.

    Feed it the step's aux output (a `MoEAux`, or any mapping/
    NamedTuple carrying aux_loss / drop_fraction fields — device
    arrays are fine, they are floated here) once per logging window;
    `MetricsLogger(moe=recorder)` then stamps `moe_aux_loss` /
    `moe_drop_fraction` (+ `moe_gate_entropy` when present) into each
    record.  Before the first update nothing is stamped — the
    OPTIONAL-never-null schema rule.
    """

    def __init__(self):
        self._last = None

    def update(self, aux) -> None:
        if hasattr(aux, "_asdict"):
            aux = aux._asdict()
        # accept BOTH spellings: a raw MoEAux (field names) and the
        # model's stats dict (already moe_-prefixed, what the train
        # step's aux output carries) — normalize to field names
        self._last = {
            (k[4:] if k.startswith("moe_") else k): float(v)
            for k, v in dict(aux).items()}

    def moe_record(self) -> dict:
        if not self._last:
            return {}
        out = {}
        for src, dst in (("aux_loss", "moe_aux_loss"),
                         ("drop_fraction", "moe_drop_fraction"),
                         ("gate_entropy", "moe_gate_entropy"),
                         ("z_loss", "moe_z_loss")):
            if src in self._last:
                out[dst] = self._last[src]
        return out
