"""The Gated DeltaNet decode recurrence's share of its roofline: the least
time the chip needs for the traced decode steps' recurrence (each live
row's float32 state read and written once per linear layer, with its q,
k, v, gates and output row; ``work_hybrid.gated_delta_needs``) over the
device time of the ``gated_delta_decode`` kernel inside the decode
program."""

from benchmark import readers, work, work_hybrid

KERNELS = ("gated_delta_decode",)


def read(ctx):
    if not getattr(ctx.dm, "lin_v_heads", 0) or not ctx.decode_kv_lens:
        return None
    t = readers.kernel_time_in(ctx, readers.DECODE_PROGRAM, KERNELS)
    if not t:
        return None
    rows = sum(len(lens) for lens in ctx.decode_kv_lens)
    need = work.needed_time(*work_hybrid.gated_delta_needs(ctx.dm, rows), ctx.peak)
    return 100.0 * need / t
