"""ff_dense_roofline: the least time the chip could take for every
``ff_dense`` call of the traced job (from each call's required
operations and bytes, ``bench.flops``), over the kernel's device
time in the trace. Nothing is read unless the trace holds exactly the
calls the job makes."""

KERNEL = "ff_dense"


def read(ctx):
    calls = [c for c in ctx.job_calls if c.kernel == KERNEL]
    seconds, count = ctx.reduced.by_kernel.get(KERNEL, (0.0, 0))
    if not seconds or count != sum(c.count for c in calls):
        return None
    least = sum(c.cost.least_seconds(ctx.peak) * c.count for c in calls)
    return 100.0 * least / seconds
