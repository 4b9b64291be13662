"""Bytes of blocks or partials moved from one chip to another outside
the all-reduce, per execution, in MB/query: the mean of
``QueryStats.cross_chip_bytes`` over the window's executions.  Data
colocation keeps it 0: each block folds on its owner and its partial is
summed there.  A program without the counter reads nothing.  A report
shared by coalesced queries counts once."""


def read(ctx):
    moved = [r.query.cross_chip_bytes
             for r in {id(r): r for r in ctx.reports
                       if r is not None}.values()
             if r.query is not None and hasattr(r.query, "cross_chip_bytes")]
    if not moved:
        return None
    return sum(moved) / len(moved) / 1e6
