"""Mean over the window's executions of the ``blockstore.fetch`` spans'
summed self time: block-store host time, in ms/query: block lookup and
partial-cache get and put (a block's commit is a child span, not
counted). Read from each query's ``RunReport.trace``; a report shared by
coalesced queries counts once."""


def read(ctx):
    traces = [t for t in {id(r): getattr(r, "trace", None)
                          for r in ctx.reports if r is not None}.values()
              if t is not None]
    if not traces:
        return None
    return 1e3 * sum(t.self_s("blockstore.fetch") for t in traces) / len(traces)
