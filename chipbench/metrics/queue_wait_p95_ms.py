"""95th percentile over requests of the wait from the due time to the
call of the request's slot prefill (admission).  In a traced run the
requests whose wait overlaps the profiler's start or stop are left out:
each holds the host for seconds, and those requests wait for the
profiler, not for admission."""

from chipbench.reading import request_times
from chipbench.stats import percentile


def read(rec):
    stalls = rec.tracer.stalls() if rec.tracer is not None else []
    v = request_times(rec, "admitted", stalls)
    return percentile(v, 95) * 1e3 if v else None
