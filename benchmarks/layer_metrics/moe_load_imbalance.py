"""The fullest held expert's assignments over the mean of the held
experts', the largest over the expert layers: 1 is even routing.  Read
outside the window from a forward of the run's last batch
(`counters["moe_counts"]`, a row an expert layer)."""


def compute(observed):
    counts = observed.get("counters", {}).get("moe_counts")
    if not counts:
        return None
    return max(max(row) * len(row) / sum(row) for row in counts if sum(row))
