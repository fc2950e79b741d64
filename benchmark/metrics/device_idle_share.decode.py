"""Share of the traced window in which no operation ran on the device
(decode cells): 1 - union of device op intervals / traced window."""

from benchmark import readers


def read(ctx):
    return readers.idle_share(ctx)
