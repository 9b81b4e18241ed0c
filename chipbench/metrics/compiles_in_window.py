"""Programs JAX lowered between the opening of the window and the end of
the drain, eager operations included (each is a trace and a compile or a
load from the persistent cache)."""


def read(rec):
    return rec.lowered_in_window
