"""Share of the window's fused-kernel block folds that took the kernel's
row-sum schedule (ungrouped folds), in %: ``kernel_folds_rowsum /
(kernel_folds_rowsum + kernel_folds_onehot)`` summed over the window's
``RunReport.mapreduce`` (a report shared by coalesced queries counts
once).  A program without these counters reads nothing."""


def read(ctx):
    reports = {id(r): r for r in ctx.reports if r is not None}
    rowsum = onehot = 0
    counted = False
    for r in reports.values():
        mr = r.mapreduce
        if mr is None or not hasattr(mr, "kernel_folds_rowsum"):
            continue
        counted = True
        rowsum += mr.kernel_folds_rowsum
        onehot += mr.kernel_folds_onehot
    if not counted or not rowsum + onehot:
        return None
    return 100.0 * rowsum / (rowsum + onehot)
