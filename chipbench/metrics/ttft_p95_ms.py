"""95th percentile of time to first token over every request due in the
window, from its due time."""

from chipbench.reading import request_times
from chipbench.stats import percentile


def read(rec):
    return percentile(request_times(rec, "first"), 95) * 1e3
