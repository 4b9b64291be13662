"""95th percentile (nearest rank) of the traced window's query latencies,
in ms: each query due in the window, from its due time to its answer's
arrays on the host, a failed or never-answered query counting as
infinitely late.  Host stalls of the machine move it by seconds from run
to run, so it is read per layer and carries no bound."""

from bench.harness import nearest_rank


def read(ctx):
    lat = getattr(ctx, "latency_s", None)
    return nearest_rank(lat, 0.95) * 1e3 if lat else None
