"""Host time per decode step that copies the logits to the host and lays
them out there, once the device has finished: the mean of the program's
engine/decode_logits span."""

from benchmark import spans


def read(ctx):
    return spans.mean_ms(ctx, "engine/decode_logits")
