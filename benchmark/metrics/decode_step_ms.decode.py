"""Device time of the decode program per run of it, from the trace."""

from benchmark import readers


def read(ctx):
    progs = readers.programs(ctx, readers.DECODE_PROGRAM)
    if progs is None:
        return None
    return progs.total() * 1e-6 / len(progs)
