"""Share of admitted prompt tokens the radix prefix cache served, so that
they were not prefilled: 1 - prefilled_tokens / prompt_tokens, from the
scheduler's counters over the whole run."""


def read(rec):
    m = rec.sched
    if not m["prompt_tokens"]:
        return None
    return 100.0 * (1.0 - m["prefilled_tokens"] / m["prompt_tokens"])
