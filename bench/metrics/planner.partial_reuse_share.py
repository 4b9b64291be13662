"""Share of the window's block partials served from the cache rather
than folded (``SessionMetrics`` ``partials_reused / (partials_reused +
partials_folded)``, window deltas), in %."""


def read(ctx):
    reused = ctx.session.get("partials_reused", 0)
    total = reused + ctx.session.get("partials_folded", 0)
    if not total:
        return None
    return 100.0 * reused / total
