"""Device time of the merge+finalize programs in the window's trace, per
answer completed, in ms."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr["merge_s"] <= 0 or not ctx.answered:
        return None
    return 1e3 * tr["merge_s"] / ctx.answered
