"""The fullest held expert's assignments over the mean of the held
experts', the largest over the expert layers: 1 is even routing.  Read
outside the window from the model's `routing_counts` on the run's last
batch (`counters["moe_counts"]`, a row an expert layer): `moe_load_
imbalance` under this cell's name, whose list of cells is the
benchmark's."""

from benchmarks.layer_metrics.moe_load_imbalance import compute  # noqa: F401
