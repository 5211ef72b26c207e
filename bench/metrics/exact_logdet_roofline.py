"""The exact logdet's share of its roofline, in percent: the least time
the chip could take for the work an exact logdet needs (``work/lu.py``:
the larger of 2/3 N^3 operations over the peak operation rate and one
read of the matrix over the peak HBM bandwidth, ``peaks.json``) over the
device time per logdet (busy seconds of the traced window over its
calls).  The work is fixed by N, so the share cannot pass 100 unless the
peaks or the time are wrong."""


def read(ctx):
    busy = ctx.trace.busy_s
    if busy <= 0 or ctx.calls <= 0:
        return None
    lu = ctx.work("lu")
    least = max(lu.flops(ctx.n) / ctx.peaks["flops_per_s"],
                lu.bytes_moved(ctx.n) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (busy / ctx.calls)
