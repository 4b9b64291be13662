"""95th percentile (nearest rank) of the window's device waits, in ms:
each execution's ``RunReport.trace.device_s``, from its merge enqueued
to its results ready on the device (stamped by ``GridFrontend``'s
watcher).  A report shared by coalesced queries counts once."""

from bench.harness import nearest_rank


def read(ctx):
    traces = {id(r): getattr(r, "trace", None) for r in ctx.reports
              if r is not None}.values()
    waits = [t.device_s for t in traces
             if t is not None and t.device_s is not None]
    return nearest_rank(waits, 0.95) * 1e3 if waits else None
