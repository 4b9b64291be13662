"""Multi-site population statistics under concurrent load.

Eight client threads hammer one :class:`GridFrontend` with a mixed
workload — repeat whole-population statistics (single-flight coalescing),
per-site grouped queries with distinct programs (batched device ticks),
and a mid-run upload of a new scan batch (epoch-isolated mutation that
drains in-flight queries) — then the frontend's observability surface
shows what the serving layer shared.

    PYTHONPATH=src python examples/concurrent_clients.py
"""

import argparse
import sys
import threading
import time

sys.path.insert(0, "src")

import numpy as np

from repro.core.frontend import GridFrontend
from repro.core.grid import GridSession
from repro.core.regions import HierarchicalSplitPolicy
from repro.core.stats import CountProgram, MeanProgram, VarianceProgram
from repro.core.table import ColumnSpec, make_mip_table

N_SITES = 4
ROWS_PER_SITE = 64
PAYLOAD = (8, 8)
CLIENTS = 8


def make_sites(seed=0):
    rng = np.random.default_rng(seed)
    t = make_mip_table(
        payload_shape=PAYLOAD,
        extra_index_columns=[ColumnSpec("age", (), np.float32),
                             ColumnSpec("site", (), np.int8)],
        # region volume tracks the logical idx:size column (6-20 MB/row);
        # ~16 rows per region at this bound
        split_policy=HierarchicalSplitPolicy(max_region_bytes=2 * 10**8),
    )
    n = N_SITES * ROWS_PER_SITE
    t.upload(
        [f"site{i % N_SITES}/img{i:05d}" for i in range(n)],
        {"img": {"data": rng.normal(size=(n,) + PAYLOAD)
                 .astype(np.float32)},
         "idx": {"size": rng.integers(6_000_000, 20_000_001, n),
                 "age": rng.uniform(4, 80, n).astype(np.float32),
                 "site": (np.arange(n) % N_SITES).astype(np.int8)}},
    )
    return t


def new_scan_batch(seed):
    rng = np.random.default_rng(seed)
    keys = [f"site0/new{seed}_{j:03d}" for j in range(8)]
    n = len(keys)
    return keys, {
        "img": {"data": rng.normal(size=(n,) + PAYLOAD)
                .astype(np.float32)},
        "idx": {"size": rng.integers(6_000_000, 20_000_001, n),
                "age": rng.uniform(4, 80, n).astype(np.float32),
                "site": np.zeros(n, np.int8)}}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--queries", type=int, default=40,
                    help="queries per client")
    args = ap.parse_args()

    t = make_sites()
    s = GridSession(t, default_eta=8)
    print(f"population: {t.num_rows} rows across {N_SITES} sites "
          f"({len(t.regions)} regions)")

    with GridFrontend(s, workers=CLIENTS, tick_ms=2.0) as fe:
        # a shared plan pool: one repeat statistic + three distinct
        # programs over the same per-site grouped scan
        pop_mean = s.scan().map(MeanProgram()).reduce()
        by_site = s.scan().group_by("idx:site")
        site_plans = [by_site.map(MeanProgram()).reduce(),
                      by_site.map(VarianceProgram()).reduce(),
                      by_site.map(CountProgram()).reduce()]
        plans = [pop_mean] * 3 + site_plans     # repeat-heavy mix

        errors = []
        reports = []
        barrier = threading.Barrier(CLIENTS + 1)

        def client(i):
            try:
                barrier.wait()
                for q in range(args.queries):
                    _, rep = fe.query(plans[(i + q) % len(plans)],
                                      timeout=120)
                    reports.append(rep)
            except BaseException as e:   # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(CLIENTS)]
        for th in threads:
            th.start()
        barrier.wait()
        t0 = time.perf_counter()

        # mid-run mutation: a new scan batch lands at site 0 while the
        # clients keep querying — drains in-flight work, bumps the epoch
        time.sleep(0.1)
        keys, data = new_scan_batch(seed=1)
        fe.upload(keys, data)
        print(f"mid-run upload of {len(keys)} rows applied at "
              f"epoch {s.epoch}")

        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]

        stats = fe.stats.snapshot()
        # service time of each execution (coalesced queries share one):
        # its queue wait plus its run under the read lock
        traces = {id(r.trace): r.trace for r in reports}.values()
        lat = sorted(t.queue_s + t.total_s("grid.execute") for t in traces)
        p50, p99 = lat[len(lat) // 2], lat[(len(lat) * 99) // 100]
        total = CLIENTS * args.queries
        print(f"\n{total} queries from {CLIENTS} clients in "
              f"{wall:.2f}s ({total / wall:,.0f} queries/s)")
        print(f"  served={stats.served} coalesce_hits="
              f"{stats.coalesce_hits} "
              f"({stats.coalesce_hits / max(stats.submitted, 1):.0%} of "
              f"submissions shared a flight)")
        print(f"  batch_merges={stats.batch_merges} "
              f"batched_queries={stats.batched_queries} "
              f"ticks={stats.ticks} "
              f"partial_coalesce_hits={stats.partial_coalesce_hits}")
        print(f"  mutations={stats.mutations} "
              f"queue_depth_peak={stats.queue_depth_peak} "
              f"p50={p50 * 1e3:.2f}ms p99={p99 * 1e3:.2f}ms")

        # the whole stream hit the device as a handful of executions
        print(f"  session scans={s.metrics.scans} "
              f"(executions for {total} queries), "
              f"block folds={s.blocks.stats.folds}")

        val, _ = fe.query(pop_mean, timeout=120)
        print(f"\npopulation mean checksum: "
              f"{float(np.asarray(val).sum()):+.4f} "
              f"over {t.num_rows} rows")


if __name__ == "__main__":
    main()
