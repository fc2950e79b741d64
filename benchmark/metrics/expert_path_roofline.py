"""The expert path's share of its roofline in decode: the least time the
chip needs for the routed experts' work of the traced decode steps (from
their per-layer expert counts: the weights of every expert with an
assignment plus token rows in and out; 2*3*d*f FLOPs per assignment) over
the device time of the expert path inside the decode program: the expert
kernels and the copies of each layer's whole expert stack."""

from benchmark import readers, work


def read(ctx):
    t = readers.expert_path_time(ctx)
    if not t or not ctx.decode_counts:
        return None
    need = 0.0
    for c in ctx.decode_counts:
        need += work.needed_time(*work.expert_needs(ctx.dm, c), ctx.peak)
    return 100.0 * need / t
