"""The delta rule's share of its roofline in a cell whose rows are
packed documents: the least time the chip could take for the
recurrence a step requires (`benchmarks/lib/work_kimi_linear.py::
scan_work`: the recurrence a token at a time over every KDA layer's
heads and the row's tokens, inputs and gradients through HBM once; a
boundary takes nothing off it) over `kda_scan_packed_ms`.  What
`kda_scan_roofline_pct` reads in the unpacked cell."""

from benchmarks.layer_metrics import kda_scan_roofline_pct

compute = kda_scan_roofline_pct.compute
