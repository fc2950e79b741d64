"""Host time per engine step outside the compiled calls: engine/step
minus its engine/prefill and engine/decode spans (the program's telemetry)."""

from benchmark import readers


def read(ctx):
    return readers.host_ms_per_step(ctx)
