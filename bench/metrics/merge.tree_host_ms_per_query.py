"""Mean over the window's executions of the tree merge's host time, in
ms/query: the summed self time of the ``merge.presum`` (owner-local sums
and the partials' ``device_put`` to their owners) and
``merge.allreduce`` (assembling the all-reduce's input and launching the
psum + finalize) spans.  Reads 0 where no merge took the tree; a
program without these spans or ``QueryStats.cross_chip_bytes`` reads
nothing.  Read from each query's ``RunReport``; a report shared by
coalesced queries counts once."""


def read(ctx):
    reports = [r for r in {id(r): r for r in ctx.reports
                           if r is not None}.values()
               if getattr(r, "trace", None) is not None
               and hasattr(r.query, "cross_chip_bytes")]
    if not reports:
        return None
    host_s = sum(r.trace.self_s("merge.presum")
                 + r.trace.self_s("merge.allreduce") for r in reports)
    return 1e3 * host_s / len(reports)
