"""Mean assignments a held expert takes in a decode step: the traced decode
steps' per-layer expert counts (the program's ``aux.counts``, zero off the
experts a layer holds; each step's sum is what the engine/moe_held_assignments
counter adds) over decode steps x held experts x MoE layers.  It shows
whether the cell's batch gives each expert here the load it would see in the
deployment the file states."""

import numpy as np


def read(ctx):
    if not len(ctx.decode_counts):
        return None
    # a configuration without a share holds every expert
    held = getattr(ctx.dm, "n_held", ctx.dm.n_experts)
    steps = len(ctx.decode_counts)
    return float(np.sum(ctx.decode_counts)) / (steps * held * ctx.dm.n_moe_layers)
