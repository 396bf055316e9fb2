"""Backend compiles (persistent-cache loads included) while the window was
open, counted by ``bench.gen.CompileCounter``; it should read 0."""

UNIT = "count"


def read(run):
    return run.compiles_in_window
