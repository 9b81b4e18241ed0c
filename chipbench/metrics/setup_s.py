"""Seconds from the start of the process to the first due arrival:
imports, weights, engine, warm-up of every program, the schedule."""


def read(rec):
    return rec.setup_s
