"""Committed device payload at the window's end
(``BlockStoreStats.device_bytes``), in GB."""


def read(ctx):
    return ctx.device_bytes / 1e9
