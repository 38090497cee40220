"""Backend compiles counted by the program's RecompileSentinel between
the window's first dispatch and its end.  Nothing may compile there."""


def read(ctx):
    return ctx["counters"].get("compiles_in_window")
