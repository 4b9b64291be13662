"""Use case 2 end-to-end: population template via the GridSession facade.

The paper's §2.2 pipeline on a real (CPU) mesh: synthetic T1 population in
a TensorTable behind a :class:`GridSession`, greedy placement, chunk size η*
from the eq. (1)-(8) model (TPU-translated constants), then ``session.run``
averages the dataset with the Pallas streaming-stats kernel as the map fold —
validated against the jnp oracle, with the byte accounting the colocation
claim rests on.  The second ``run`` shows the compiled-plan cache: same
program + same epoch = no new executable.

    PYTHONPATH=src python examples/population_stats.py --scale 0.05
"""

import argparse
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.core.chunk_model import ChunkModel, tpu_chunk_params
from repro.core.grid import GridSession
from repro.core.stats import MeanProgram, VarianceProgram
from repro.data.pipeline import synthetic_image_population
from repro.kernels.streaming_stats.ops import KernelMeanProgram


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05,
                    help="fraction of the 5,153-subject population")
    ap.add_argument("--payload", type=int, default=8,
                    help="volume side (payload = side^3 voxels)")
    args = ap.parse_args()

    table = synthetic_image_population(
        payload_shape=(args.payload,) * 3, scale=args.scale)
    print(f"population: {table.num_rows} subjects, "
          f"{table.total_bytes()/1e9:.1f} GB logical "
          f"({len(table.regions)} regions)")

    session = GridSession(table)
    D = session.mesh.shape["data"]

    # chunk size from the TPU-translated model
    row_bytes = float(np.mean(table.row_bytes()))
    cm = ChunkModel(tpu_chunk_params(
        n_img=table.num_rows, row_bytes=row_bytes, n_devices=D))
    try:
        lo, hi = cm.eta_bounds()
        eta, pred = cm.optimal_eta()
        print(f"chunk model: eta in [{lo}, {hi}], eta*={eta} "
              f"(predicted wall {pred*1e3:.2f} ms at TPU rates)")
    except ValueError as e:
        # single-wave window empty on this tiny device count: run multi-wave
        # at the memory-bound chunk size (the engine handles extra rounds)
        hi = int(cm.p.mem / cm.p.size_big)
        eta = max(min(hi, 512), 1)
        print(f"chunk model: {e}\n  -> multi-wave fallback, eta={eta}")

    # interpret mode is the CPU harness; on a TPU the kernel compiles
    kmean = KernelMeanProgram(interpret=jax.default_backend() != "tpu")
    mean_k, report = session.run(kmean, eta=eta)
    stats = report.mapreduce
    mean_ref = table.column("img", "data").mean(axis=0)
    err = float(np.abs(np.asarray(mean_k) - mean_ref).max())
    print(f"\nkernel mean over {stats.local_rows_read} rows: "
          f"max err vs numpy = {err:.2e}")
    print(f"  local payload bytes read : {stats.local_bytes_read:,}")
    print(f"  shuffle bytes (network)  : {stats.shuffle_bytes:,}  "
          f"({stats.shuffle_bytes/max(stats.local_bytes_read,1)*100:.3f}% "
          f"of payload — the colocation win)")
    print(f"  rounds={stats.rounds} chunks={stats.chunks} eta={eta}")

    compiles_before = session.engine.compile_count
    _, report2 = session.run(kmean, eta=eta)
    print(f"repeat run: plan_cache_hit={report2.plan_cache_hit}, "
          f"new compiles={session.engine.compile_count - compiles_before}")

    var, _ = session.run(VarianceProgram(), eta=eta)
    verr = float(np.abs(np.asarray(var["var"])
                        - table.column("img", "data").var(axis=0)).max())
    print(f"variance (Chan parallel merge): max err = {verr:.2e}")

    # --- grouped analytics: per-stratum mean/variance in ONE pass --------
    # Real cohorts are stratified (per-site, per-scanner, per-sex): one
    # group_by plan folds group-keyed partials per block instead of one
    # query per stratum — same gathers, same partial cache, G answers.
    grouped, grep = (session.scan().select("img:data").group_by("idx:sex")
                     .map(MeanProgram()).map(VarianceProgram())
                     .reduce().collect(eta=eta))
    data = table.column("img", "data")
    sexes = table.column("idx", "sex")
    gmean, gvar = grouped.values
    print(f"\ngrouped (per-sex) stats over {grep.query.num_groups} strata "
          f"in one pass (gathers={grep.query.gather_count}):")
    for g, sex in enumerate(grouped.keys):
        ref = data[sexes == sex]
        gerr = float(np.abs(np.asarray(gmean)[g] - ref.mean(0)).max())
        print(f"  sex={int(sex)}: n={len(ref)}, "
              f"mean max err vs numpy groupby = {gerr:.2e}")
    _, grep2 = (session.scan().select("img:data").group_by("idx:sex")
                .map(MeanProgram()).map(VarianceProgram())
                .reduce().collect(eta=eta))
    print(f"repeat grouped query: rows_folded={grep2.query.rows_folded} "
          f"(group-keyed partials cached)")
    print()
    print(session.describe())


if __name__ == "__main__":
    main()
