"""Host time per decode step that samples the next tokens from the copied
logits and appends them: the mean of the program's engine/decode_sample
span."""

from benchmark import spans


def read(ctx):
    return spans.mean_ms(ctx, "engine/decode_sample")
