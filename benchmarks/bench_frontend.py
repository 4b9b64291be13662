"""GridFrontend serving bench — the PR-7 wall-clock acceptance.

Closed-loop client threads drive one :class:`GridFrontend` through three
mixes, each run twice — coalescing ON vs OFF (the no-coalesce control
executes every query independently, like clients sharing a bare session
behind a lock-free thread pool):

1. **repeat-heavy**   — every client re-asks the same warm statistic; the
   single-flight registry should collapse the stream to ~zero executions
   (the gated ``coalesce_speedup_repeat`` ratio).
2. **group-by-heavy** — clients cycle distinct programs over one grouped
   scan; the tick scheduler merges them into shared fused passes.
3. **mutation-interleaved** — the repeat mix with periodic uploads
   draining in-flight queries; measures serving under epoch churn.

Reported per arm: sustained queries/sec, p50/p99 service latency,
coalesce ratio (hits / submissions).  Artifact: ``BENCH_frontend.json``
via benchmarks/run.py (also in ``--smoke``; CI gates
``coalesce_speedup_repeat`` via perf_baselines.json).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from repro.core.frontend import GridFrontend
from repro.core.grid import GridSession
from repro.core.regions import HierarchicalSplitPolicy
from repro.core.stats import CountProgram, MeanProgram, VarianceProgram
from repro.core.table import ColumnSpec, make_mip_table

N_ROWS = 256
PAYLOAD = (8, 8)
ETA = 8
CLIENTS = 8
QUERIES_SMOKE = 30           # per client per arm
QUERIES_FULL = 120
TICK_MS = 1.0
MUTATION_ROUNDS = 4


def _make_table(seed=0):
    rng = np.random.default_rng(seed)
    t = make_mip_table(
        payload_shape=PAYLOAD,
        extra_index_columns=[ColumnSpec("age", (), np.float32),
                             ColumnSpec("sex", (), np.int8)],
        split_policy=HierarchicalSplitPolicy(max_region_bytes=4096),
    )
    n = N_ROWS
    t.upload(
        [f"img{i:05d}" for i in range(n)],
        {"img": {"data": rng.normal(size=(n,) + PAYLOAD)
                 .astype(np.float32)},
         "idx": {"size": rng.integers(6_000_000, 20_000_001, n),
                 "age": rng.uniform(4, 80, n).astype(np.float32),
                 "sex": rng.integers(0, 2, n).astype(np.int8)}},
    )
    return t


def _mutation_batch(r, seed):
    rng = np.random.default_rng(seed)
    keys = [f"mut{r}_{j}" for j in range(2)]
    n = len(keys)
    return keys, {
        "img": {"data": rng.normal(size=(n,) + PAYLOAD)
                .astype(np.float32)},
        "idx": {"size": rng.integers(6_000_000, 20_000_001, n),
                "age": rng.uniform(4, 80, n).astype(np.float32),
                "sex": rng.integers(0, 2, n).astype(np.int8)}}


def _drive(fe: GridFrontend, plans, queries_per_client: int,
           mutate: bool = False) -> dict:
    """Closed loop: CLIENTS threads each issue ``queries_per_client``
    queries round-robin over ``plans``; optionally a mutator thread
    uploads between rounds.  Returns qps/latency/coalesce numbers."""
    errors = []
    reports = []
    served0 = fe.stats.snapshot().served       # warm-up queries
    t_start = time.time_ns()
    barrier = threading.Barrier(CLIENTS + 1)

    def client(i):
        try:
            barrier.wait()
            for q in range(queries_per_client):
                plan = plans[(i + q) % len(plans)]
                _, rep = fe.query(plan, timeout=300)
                reports.append(rep)
        except BaseException as e:   # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    if mutate:
        for r in range(MUTATION_ROUNDS):
            time.sleep(0.02)
            keys, data = _mutation_batch(r, seed=r + 100)
            fe.upload(keys, data, on_duplicate="overwrite")
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    stats = fe.stats.snapshot()
    # service time of each execution submitted after the warm-up
    # (coalesced queries share one): its queue wait plus its run under the
    # read lock
    traces = {id(r.trace): r.trace for r in reports
              if r.trace.submit_ns >= t_start}.values()
    lat = sorted(t.queue_s + t.total_s("grid.execute") for t in traces)
    lat = lat or [0.0]
    p50, p99 = lat[len(lat) // 2], lat[(len(lat) * 99) // 100]
    total = CLIENTS * queries_per_client
    assert stats.served - served0 == total, (stats.served, served0, total)
    return {
        "queries": total,
        "wall_s": wall,
        "qps": total / max(wall, 1e-9),
        "p50_ms": p50 * 1e3,
        "p99_ms": p99 * 1e3,
        "coalesce_ratio": stats.coalesce_hits / max(stats.submitted, 1),
        "batch_merges": stats.batch_merges,
        "ticks": stats.ticks,
        "mutations": stats.mutations,
    }


def _arm(make_plans, queries: int, coalesce: bool,
         mutate: bool = False) -> dict:
    """One (mix, mode) measurement on a fresh session — cold caches for
    both modes, one warm-up pass so the gated ratio compares steady-state
    serving, not first-touch compilation."""
    s = GridSession(_make_table(), default_eta=ETA)
    plans = make_plans(s)
    with GridFrontend(s, workers=CLIENTS, tick_ms=TICK_MS,
                      max_pending=4 * CLIENTS * len(plans),
                      coalesce=coalesce) as fe:
        for plan in plans:                       # warm: compile + caches
            fe.query(plan, timeout=300)
        return _drive(fe, plans, queries, mutate=mutate)


def run(verbose: bool = True, smoke: bool = True) -> dict:
    queries = QUERIES_SMOKE if smoke else QUERIES_FULL

    def repeat_plans(s):
        return [s.scan().map(MeanProgram()).reduce()]

    def grouped_plans(s):
        base = s.scan().group_by("idx:sex")
        return [base.map(MeanProgram()).reduce(),
                base.map(VarianceProgram()).reduce(),
                base.map(CountProgram()).reduce()]

    arms = {}
    # the mutation mix drives the grouped plans: each upload clears the
    # flight registry, so the post-mutation burst arrives cold with three
    # distinct programs — the tick scheduler's merge path under churn
    for mix, make_plans, mutate in (
            ("repeat", repeat_plans, False),
            ("grouped", grouped_plans, False),
            ("mutation", grouped_plans, True)):
        arms[f"{mix}_coalesced"] = _arm(make_plans, queries,
                                        coalesce=True, mutate=mutate)
        arms[f"{mix}_baseline"] = _arm(make_plans, queries,
                                       coalesce=False, mutate=mutate)

    def speedup(mix):
        return (arms[f"{mix}_coalesced"]["qps"]
                / max(arms[f"{mix}_baseline"]["qps"], 1e-9))

    out = {
        "n_rows": N_ROWS,
        "clients": CLIENTS,
        "queries_per_client": queries,
        "tick_ms": TICK_MS,
        "coalesce_speedup_repeat": speedup("repeat"),
        "coalesce_speedup_grouped": speedup("grouped"),
        "coalesce_speedup_mutation": speedup("mutation"),
        **{f"{arm}_{k}": v for arm, d in arms.items()
           for k, v in d.items()},
    }
    # acceptance: coalesced serving at least doubles repeat throughput
    assert out["coalesce_speedup_repeat"] >= 2.0, (
        arms["repeat_coalesced"], arms["repeat_baseline"])
    if verbose:
        for mix in ("repeat", "grouped", "mutation"):
            c, b = arms[f"{mix}_coalesced"], arms[f"{mix}_baseline"]
            print(f"{mix:>9}: {c['qps']:8.0f} qps coalesced "
                  f"(p50={c['p50_ms']:.2f}ms p99={c['p99_ms']:.2f}ms, "
                  f"coalesce={c['coalesce_ratio']:.2f}, "
                  f"merges={c['batch_merges']}) vs "
                  f"{b['qps']:8.0f} qps baseline -> "
                  f"{speedup(mix):.1f}x")
    return out


if __name__ == "__main__":
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="CI-fast query counts")
    args = parser.parse_args()
    out = run(smoke=args.smoke)
    with open("BENCH_frontend.json", "w") as f:
        json.dump({"bench": "frontend", **out}, f, indent=2, sort_keys=True)
    print("wrote BENCH_frontend.json")
