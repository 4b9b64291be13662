"""Mean over the window's executions of the ``fold.dispatch`` spans' summed
self time: host time enqueueing the per-block folds, in ms/query (one
span per folded block). Read from each query's ``RunReport.trace``; a
report shared by coalesced queries counts once."""


def read(ctx):
    traces = [t for t in {id(r): getattr(r, "trace", None)
                          for r in ctx.reports if r is not None}.values()
              if t is not None]
    if not traces:
        return None
    return 1e3 * sum(t.self_s("fold.dispatch") for t in traces) / len(traces)
