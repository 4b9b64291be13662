"""Blocks moved host to device for the window's queries: the sum of
``QueryStats.blocks_transferred`` over their reports (a report shared by
coalesced queries counts once)."""


def read(ctx):
    reports = {id(r): r for r in ctx.reports if r is not None}
    return float(sum(r.query.blocks_transferred for r in reports.values()
                     if r.query is not None))
