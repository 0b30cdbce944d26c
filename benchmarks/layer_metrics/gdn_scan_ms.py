"""Device milliseconds a step owned by the delta rule's scope
(`block*/attn/scan`) in the Gated DeltaNet cell, every such layer,
forward and backward, first device: what `kda_scan_ms` reads in the
KDA cells, the loops' own events left out, here with one decay a head
and 96-wide keys over 192-wide values.  None on a program that opens
no such scope."""

from benchmarks.layer_metrics import kda_scan_ms

compute = kda_scan_ms.compute
