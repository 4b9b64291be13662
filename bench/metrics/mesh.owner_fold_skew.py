"""How unevenly the window's block folds fell over the owner chips, in
%: 100 x (the sum over executions of the most folds one owner ran, over
the sum of the mean folds per owner, minus 1), from
``QueryStats.folds_per_owner``.  0 when every execution's folds spread
evenly; the busiest chip sets an answer's time.  A program without
``folds_per_owner``, or a window that folded nothing, reads nothing.  A
report shared by coalesced queries counts once."""


def read(ctx):
    spreads = [r.query.folds_per_owner
               for r in {id(r): r for r in ctx.reports
                         if r is not None}.values()
               if r.query is not None
               and getattr(r.query, "folds_per_owner", ())]
    busiest = sum(max(f) for f in spreads)
    mean = sum(sum(f) / len(f) for f in spreads)
    if not mean:
        return None
    return 100.0 * (busiest / mean - 1.0)
