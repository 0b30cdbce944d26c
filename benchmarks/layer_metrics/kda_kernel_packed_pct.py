"""Percent of the traced `gated_delta_rule` calls whose chunk-local
stage took the Pallas pair, in a cell whose rows are packed documents:
what `kda_kernel_pct` reads in the unpacked cell.  100, or the resets
pushed the op off its kernels."""

from benchmarks.layer_metrics import kda_kernel_pct

compute = kda_kernel_pct.compute
