"""Backend compiles (JAX's executable builds, persistent-cache loads
included) recorded by the window's queries: the sum of
``RunReport.trace.compiles``.  Every shape is warmed in set-up, so this
reads 0; where it does not, each record's spans name the step."""


def read(ctx):
    traces = [t for t in {id(r): getattr(r, "trace", None)
                          for r in ctx.reports if r is not None}.values()
              if t is not None]
    return float(sum(t.compiles for t in traces)) if traces else None
