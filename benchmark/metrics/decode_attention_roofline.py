"""The decode-attention kernel's share of its roofline: live K and V
bytes of the traced decode steps over HBM bandwidth, divided by the
kernel's device time inside the decode program."""

from benchmark import readers, work


def read(ctx):
    if ctx.dm.attn != "gqa" or not ctx.decode_kv_lens:
        return None
    t = readers.kernel_time_in(ctx, readers.DECODE_PROGRAM, readers.ATTENTION_KERNELS)
    if not t:
        return None
    nbytes = sum(work.kv_bytes(ctx.dm, lens) for lens in ctx.decode_kv_lens)
    return 100.0 * nbytes / ctx.peak["hbm_bytes_per_s"] / t
