"""Median host milliseconds inside one `step(...)` call of the window.
The call returns before the device is done, so this is the cost of
dispatch; it moves nothing end to end while it stays under the step."""

import statistics


def compute(observed):
    spans = observed["spans"].get("dispatch_s")
    return 1e3 * statistics.median(spans) if spans else None
