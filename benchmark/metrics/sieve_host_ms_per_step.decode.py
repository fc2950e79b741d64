"""Host time per decode step of the Sieve scheduler's per-layer pass over
the step's expert counts, already on the host: the mean of the program's
engine/sieve_host span."""

from benchmark import spans


def read(ctx):
    return spans.mean_ms(ctx, "engine/sieve_host")
