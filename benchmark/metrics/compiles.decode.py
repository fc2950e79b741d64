"""Programs built (compiled, or loaded from the persistent compile cache)
inside the window: the program's engine/compile/* spans.  Every shape is
warmed in set-up, so a steady window reads 0."""

from benchmark import spans


def read(ctx):
    return spans.compiles(ctx)
