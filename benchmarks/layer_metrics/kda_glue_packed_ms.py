"""Device milliseconds a step owned by what a KDA layer spends on
neither its GEMMs nor the delta rule (`block*/attn/conv`,
`attn/decay`, `attn/onorm`) in a cell whose rows are packed documents:
what `kda_glue_ms` reads in the unpacked cell, here with the taps'
boundary masks among the convolution's work.  None on a program that
opens no such scope."""

from benchmarks.layer_metrics import kda_glue_ms

compute = kda_glue_ms.compute
