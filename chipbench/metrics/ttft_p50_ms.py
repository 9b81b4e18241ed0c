"""Median time to first token over every request due in the window, from
its due time to the segment boundary that delivered its first token."""

from chipbench.reading import request_times
from chipbench.stats import percentile


def read(rec):
    return percentile(request_times(rec, "first"), 50) * 1e3
