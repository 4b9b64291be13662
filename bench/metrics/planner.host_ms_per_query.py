"""Mean over the window's executions of the ``grid.plan`` spans' summed
self time: planner host time, in ms/query: scan mask, work list, mask
signatures, group mapping and result-cache probe. Read from each query's
``RunReport.trace``; a report shared by coalesced queries counts once."""


def read(ctx):
    traces = [t for t in {id(r): getattr(r, "trace", None)
                          for r in ctx.reports if r is not None}.values()
              if t is not None]
    if not traces:
        return None
    return 1e3 * sum(t.self_s("grid.plan") for t in traces) / len(traces)
