"""Device milliseconds a step owned by the delta rule's scope
(`block*/attn/scan`) in a cell whose rows are packed documents, every
KDA layer, forward and backward, first device: what `kda_scan_ms` reads
in the unpacked cell, the loops' own events left out, here with a
document's resets inside the op.  None on a program that opens no such
scope."""

from benchmarks.layer_metrics import kda_scan_ms

compute = kda_scan_ms.compute
