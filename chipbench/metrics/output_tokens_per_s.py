"""Output tokens delivered to the host inside the window, over the
window's seconds: every request's, the lead-in's included, since the
server delivered them in the window."""

from chipbench.stats import rate


def read(rec):
    return rate(sum(r.tokens_in_window for r in rec.recs.values()),
                rec.seconds)
