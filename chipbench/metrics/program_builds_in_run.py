"""Programs the serving engine built from the lead-in's first arrival to
the drain's end: dispatches at a shape key it had not run before (a slot
prefill's length, a decode segment's steps x table width, a copy-on-write
copy's pair count), the scheduler's ``programs_built`` counter.  A
program without the counter reads nothing."""


def read(rec):
    return rec.sched.get("programs_built")
