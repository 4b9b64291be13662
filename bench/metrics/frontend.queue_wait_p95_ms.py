"""95th percentile (nearest rank) of the window's queue waits, in ms:
each execution's ``RunReport.trace.queue_s``, from admission in
``GridFrontend`` to its execution holding the read lock (tick, worker
and lock wait).  A report shared by coalesced queries counts once."""

from bench.harness import nearest_rank


def read(ctx):
    traces = {id(r): getattr(r, "trace", None) for r in ctx.reports
              if r is not None}.values()
    waits = [t.queue_s for t in traces
             if t is not None and t.queue_s is not None]
    return nearest_rank(waits, 0.95) * 1e3 if waits else None
