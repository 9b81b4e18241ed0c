"""95th percentile over requests of each request's time per output token
after the first: (last delivery - first delivery) / (tokens - 1), for
every request due in the window that received two tokens or more."""

from chipbench.reading import measured
from chipbench.stats import percentile, tpot


def read(rec):
    v = [tpot(r.first, r.last, r.tokens) for r in measured(rec)
         if r.tokens >= 2]
    return percentile(v, 95) * 1e3 if v else None
