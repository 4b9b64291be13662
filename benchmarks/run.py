"""Benchmark harness — one entry per paper table/figure + roofline/kernels.

Prints ``name,value,derived`` CSV lines per benchmark plus the validation
summary EXPERIMENTS.md quotes, and writes one JSON artifact per bench
(``BENCH_<name>.json``) so the perf trajectory is diffable across PRs.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI-fast subset

``--smoke`` runs every artifact-emitting bench except the table-scheme
sweep and the roofline (balancer, chunk model, kernels, query pruning,
blockstore, fold engine, group_by, frontend, tiers, faults, sketches) —
CI uploads the JSON files from each
run and gates headline metrics against ``benchmarks/perf_baselines.json``
via ``benchmarks/check_regression.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Callable, Optional

#: the checkout root: the persistent compilation cache lives under it
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_artifact(name: str, payload: dict) -> None:
    path = f"BENCH_{name}.json"
    with open(path, "w") as f:
        json.dump({"bench": name, **payload}, f, indent=2, sort_keys=True)
    print(f"wrote {path}")


def _run_bench(
    name: str,
    title: str,
    runner: Callable[[], dict],
    summarize: Optional[Callable[[dict], str]] = None,
    payload: Optional[Callable[[dict], dict]] = None,
) -> None:
    """Time one bench, print its CSV summary line, write its artifact."""
    print(f"\n--- {title} ---")
    t0 = time.perf_counter()
    b = runner()
    elapsed_us = round((time.perf_counter() - t0) * 1e6)
    if summarize is not None:
        print(f"bench_{name},{elapsed_us},{summarize(b)}")
    _write_artifact(name, {"elapsed_us": elapsed_us,
                           **(payload(b) if payload else b)})


def run_balancer() -> None:
    from benchmarks import bench_balancer

    _run_bench(
        "balancer",
        "[Fig. 3] Use case 1: heterogeneous cluster / load balancer",
        bench_balancer.run,
        lambda b: f"mean_speedup={b['mean_balancer_speedup']:.2f}x;paper=1.5x")


def run_chunk_model() -> None:
    from benchmarks import bench_chunk_model

    _run_bench(
        "chunk_model",
        "[Fig. 4] Use case 2: large-dataset average / chunk model",
        bench_chunk_model.run,
        lambda b: (f"eta_star={b['eta_star_model']};paper=50-60;"
                   f"sge_wall_x={b['sge_wall_x']:.1f};paper=5-8;"
                   f"sge_rt_x={b['sge_rt_x']:.1f};paper=14-20"))


def run_table_scheme() -> None:
    from benchmarks import bench_table_scheme

    _run_bench(
        "table_scheme",
        "[Fig. 6/Table 3] Use case 3: table scheme / rapid query",
        bench_table_scheme.run,
        lambda b: (f"naive_over_proposed_small="
                   f"{b['naive_over_proposed_small']:.1f}x;paper=9x;"
                   f"sge_over_proposed_large="
                   f"{b['sge_over_proposed_large']:.1f}x;paper=3x"))


def run_query_pruning() -> None:
    from benchmarks import bench_query_pruning

    _run_bench(
        "query_pruning",
        "[PR 2] GridQuery region pruning: pruned vs naive scan",
        bench_query_pruning.run,
        lambda b: (f"regions_pruned={b['regions_pruned']}/{b['n_sites']};"
                   f"wall_vs_mask={b['wall_speedup_vs_mask_path']:.1f}x;"
                   f"sim_rt_x={b['sim_rt_speedup']:.1f}x"))


def run_blockstore() -> None:
    from benchmarks import bench_blockstore

    def summarize(b):
        total = b["refresh_blocks_reused"] + b["refresh_blocks_transferred"]
        return (f"refresh_x={b['refresh_speedup_vs_rebuild']:.1f};"
                f"reused={b['refresh_blocks_reused']}/{total};"
                f"overlap_2nd_gathers={b['overlap_second_gathers']}")

    _run_bench(
        "blockstore",
        "[PR 3] BlockStore: copy-on-write mutation/overlap reuse",
        bench_blockstore.run,
        summarize)


def run_fold_engine() -> None:
    from benchmarks import bench_fold_engine

    _run_bench(
        "fold_engine",
        "[PR 4] Block-granular fold engine: partial cache + fused CSE",
        bench_fold_engine.run,
        lambda b: (f"warm_x={b['warm_speedup_vs_refold']:.0f};"
                   f"dirty_rows={b['dirty_rows_folded']}/{b['n_rows']};"
                   f"cse_flops={b['cse_flop_ratio']:.2f}x"))


def run_group_by() -> None:
    from benchmarks import bench_group_by

    _run_bench(
        "group_by",
        "[PR 5] Grouped analytics: one-pass group_by + tree-reduce merge",
        bench_group_by.run,
        lambda b: (f"grouped_x={b['grouped_speedup_vs_loop']:.1f};"
                   f"warm_x={b['grouped_warm_speedup_vs_loop']:.1f};"
                   f"merge_tree_x={b['merge_tree_speedup']:.2f}"))


def run_frontend(smoke: bool = True) -> None:
    from benchmarks import bench_frontend

    _run_bench(
        "frontend",
        "[PR 7] GridFrontend: concurrent serving, cross-query coalescing",
        lambda: bench_frontend.run(smoke=smoke),
        lambda b: (f"repeat_x={b['coalesce_speedup_repeat']:.1f};"
                   f"grouped_x={b['coalesce_speedup_grouped']:.1f};"
                   f"mutation_x={b['coalesce_speedup_mutation']:.1f};"
                   f"qps={b['repeat_coalesced_qps']:.0f};"
                   f"p99_ms={b['repeat_coalesced_p99_ms']:.2f}"))


def run_tiers() -> None:
    from benchmarks import bench_tiers

    _run_bench(
        "tiers",
        "[PR 8] Tiered BlockStore: spill at 10x the device budget",
        bench_tiers.run,
        lambda b: (f"warm_over_cold={b['spill_warm_over_cold']:.3f};"
                   f"warm_disk_reads={b['warm_disk_reads']};"
                   f"promote_gathers={b['promote_gathers']};"
                   f"spills={b['cold_spills']}"))


def run_faults(smoke: bool = True) -> None:
    from benchmarks import bench_faults

    _run_bench(
        "faults",
        "[PR 9] Fault tolerance: armed-injector overhead + recovery walls",
        lambda: bench_faults.run(smoke=smoke),
        lambda b: (f"overhead_x={b['fault_overhead_ratio']:.3f};"
                   f"corrupt_recover_s={b['corrupt_recovery_wall_s']:.2f};"
                   f"quarantine_recover_s="
                   f"{b['quarantine_recovery_wall_s']:.2f}"))


def run_sketches() -> None:
    from benchmarks import bench_sketches

    _run_bench(
        "sketches",
        "[PR 10] Sketch statistics: fold overhead, warm repeat, accuracy",
        bench_sketches.run,
        lambda b: (f"overhead_x={b['sketch_fold_overhead_vs_moments']:.2f};"
                   f"warm_rows={b['warm_rows_folded']};"
                   f"cm_frac={b['cm_overcount_frac_of_bound']:.2f};"
                   f"hll_se={b['hll_err_frac_of_se']:.2f};"
                   f"rank_frac={b['quantile_rank_err_frac_of_bound']:.2f}"))


def run_kernels() -> None:
    from benchmarks import bench_kernels

    _run_bench(
        "kernels",
        "Kernels (oracle agreement + op accounting)",
        bench_kernels.run,
        lambda b: (f"fused_fold_bytes_x="
                   f"{b['fused_fold_speedup_grouped']:.2f};"
                   f"bw_frac={b['fused_fold_roofline_bw_frac']:.2f}"),
        # rows become dicts for the artifact; every scalar metric (the
        # gated fused_fold ratios) passes through untouched
        payload=lambda b: {
            **{k: v for k, v in b.items() if k != "rows"},
            "rows": [{"name": n, "us": us, "derived": derived}
                     for n, us, derived in b["rows"]],
        })


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="CI-fast subset: every artifact bench except "
                             "the table-scheme sweep and the roofline")
    args = parser.parse_args()

    from repro.utils import use_compile_cache
    use_compile_cache(REPO)

    print("=" * 72)
    print("ColoGrid benchmarks (paper: HadoopBase-MIP backend, Bao et al. 2017)")
    print("=" * 72)

    if args.smoke:
        run_balancer()
        run_chunk_model()
        run_kernels()
        run_query_pruning()
        run_blockstore()
        run_fold_engine()
        run_group_by()
        run_frontend(smoke=True)
        run_tiers()
        run_faults(smoke=True)
        run_sketches()
        print("\nsmoke benchmarks complete")
        return

    from benchmarks import bench_roofline

    run_balancer()
    run_chunk_model()
    run_table_scheme()
    run_query_pruning()
    run_blockstore()
    run_fold_engine()
    run_group_by()
    run_frontend(smoke=False)
    run_tiers()
    run_faults(smoke=False)
    run_sketches()
    run_kernels()

    print("\n--- Roofline (single-pod dry-run artifacts) ---")
    bench_roofline.run()

    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
