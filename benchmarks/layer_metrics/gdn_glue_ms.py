"""Device milliseconds a step owned by what a Gated DeltaNet layer
spends on neither its projections' GEMMs (`attn/qkv`, `attn/gate`,
`attn/proj`) nor the delta rule (`attn/scan`): the conv stage
(`attn/conv`: taps, document masks, SiLU, the unit scaling of q and k,
the head-major order), the decay and beta (`attn/decay`, with their
3840 x 30 products), the output norm and gating (`attn/onorm`, with the
layout copies of o), forward and backward, first device: what
`kda_glue_ms` reads in the KDA cells.  None on a program that opens no
such scope."""

from benchmarks.layer_metrics import kda_glue_ms

compute = kda_glue_ms.compute
