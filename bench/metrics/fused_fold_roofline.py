"""The fused fold kernel's share of the HBM roofline, in %.

Bytes: the payload of the rows each query selected in the blocks it
folded (``MapReduceStats.local_rows_read``) plus each fold's float32
accumulators, counted from shapes by ``bench/work.py``; pad rows,
unselected rows and the one-hot contraction are not counted.  Time: the
device durations of the kernel's operations in the window's trace.  The
statistic is a few operations per element, so bandwidth bounds it."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr["kernel_s"] <= 0:
        return None
    reports = {id(r): r for r in ctx.reports if r is not None}
    rows = folds = 0
    for r in reports.values():
        if r.mapreduce is None or r.query is None:
            continue
        rows += r.mapreduce.local_rows_read
        folds += r.query.partials_total - r.query.partials_reused
    if not rows:
        return None
    nbytes = ctx.work.fold_bytes(rows, folds, ctx.features, ctx.itemsize,
                                 ctx.programs)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / tr["kernel_s"]
