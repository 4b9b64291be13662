#!/usr/bin/env python3
"""Chip smoke: the cohort-statistics path end to end on a TPU.

Builds the paper's T1 cohort (Table-3 age x sex strata, scale 0.05: 226
subjects) at full MNI152 1 mm volume width — 182 x 218 x 182 float32, 28.9 MB
per subject, 6.5 GB of payload — from a fixed seed, opens a ``GridSession``
on exactly one chip behind a ``GridFrontend``, and serves what an analyst
sends:

1. whole-cohort voxel-wise mean + variance, fused into one pass;
2. the same pair per sex (``group_by("idx:sex")``);
3. the same pair over a Table-3 subset (females aged 4-20, via ``.where``);
4. a repeat of query 1, which must fold no rows;
5. an upload of a few new volumes, then query 1 again, which must re-fold
   only the regions the upload touched.

Every answer is checked against a float64 NumPy reference computed from the
table's host columns, and every fold must take the fused Pallas kernel
(``fold_path_counts["xla"] == 0``).  The lines before the last are
observations (device, sizes, times, peak HBM, fold counts), not claims.

    python chip_smoke.py              # one chip: the phases above
    python chip_smoke.py --chips 4    # four-chip data mesh: tree vs funnel
                                      # merge and a rebalance, nothing else

The last line of standard output is one JSON object naming the device,
printed only when every phase passed.  Without a TPU the script exits
non-zero and prints no result.  The phases are plain functions, so a CPU
test can run them at a tiny volume size with ``fold_interpret=True``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402

from repro.core.balancer import NodeSpec  # noqa: E402
from repro.core.frontend import GridFrontend  # noqa: E402
from repro.core.grid import GridSession  # noqa: E402
from repro.core.query import age_sex_predicate  # noqa: E402
from repro.core.stats import MeanProgram, VarianceProgram  # noqa: E402
from repro.data.pipeline import synthetic_image_population  # noqa: E402
from repro.utils import use_compile_cache  # noqa: E402

#: MNI152 1 mm template grid: the published T1 volume width
MNI_SHAPE = (182, 218, 182)
SEED = 0
#: fraction of the paper's Table-3 population: 226 subjects, 6.5 GB at
#: MNI width — about 40% of one chip's 16 GB once committed as blocks
SCALE = 0.05
#: hierarchical split threshold over the logical 6-20 MB sizes: 8 regions
#: of 26-30 subjects, so each committed block is 32 rows (0.92 GB)
REGION_BYTES = 1 << 29
#: Table-3 subset for the pushdown query: females aged 4-20 (about a
#: quarter of the cohort, so it folds whole blocks under a row mask)
SUBSET = (4.0, 20.0, 1)
UPLOAD_ROWS = 4
#: absolute tolerances against the float64 reference.  The volumes are
#: unit-variance normals; fp32 accumulation over a few hundred rows stays
#: far inside these
MEAN_TOL = 1e-4
VAR_TOL = 1e-3
QUERY_TIMEOUT_S = 900.0


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


# ----------------------------------------------------------------------
# the cohort and its float64 reference
# ----------------------------------------------------------------------

def build_cohort(payload_shape: Tuple[int, ...] = MNI_SHAPE,
                 scale: float = SCALE, seed: int = SEED,
                 region_bytes: int = REGION_BYTES):
    return synthetic_image_population(payload_shape=payload_shape,
                                      scale=scale, seed=seed,
                                      region_bytes=region_bytes)


class CohortReference:
    """float64 power sums ``(n, s1, s2)`` per sex and for the subset,
    accumulated over the table's host columns a few rows at a time (a
    float64 copy of a full-width cohort would not fit the host)."""

    CLASSES = ("sex0", "sex1", "subset")
    #: rows converted to float64 at a time
    CHUNK = 8

    def __init__(self, payload_shape: Tuple[int, ...]):
        self.shape = tuple(payload_shape)
        f = int(np.prod(self.shape))
        self.n = {c: 0 for c in self.CLASSES}
        self.s1 = {c: np.zeros(f, np.float64) for c in self.CLASSES}
        self.s2 = {c: np.zeros(f, np.float64) for c in self.CLASSES}

    @classmethod
    def of_table(cls, table) -> "CohortReference":
        ref = cls(table.column_spec("img", "data").shape)
        ref.add_rows(table.column("img", "data"), table.column("idx", "age"),
                     table.column("idx", "sex"))
        return ref

    def add_rows(self, data, ages, sexes) -> None:
        lo_age, hi_age, sub_sex = SUBSET
        for lo in range(0, len(data), self.CHUNK):
            hi = min(lo + self.CHUNK, len(data))
            x = np.asarray(data[lo:hi], np.float64).reshape(hi - lo, -1)
            sex, age = sexes[lo:hi], ages[lo:hi]
            w = np.stack([sex == 0, sex == 1,
                          (age >= lo_age) & (age < hi_age) & (sex == sub_sex)],
                         axis=1).astype(np.float64)      # [rows, classes]
            s1 = w.T @ x
            s2 = w.T @ (x * x)
            for i, c in enumerate(self.CLASSES):
                self.n[c] += int(w[:, i].sum())
                self.s1[c] += s1[i]
                self.s2[c] += s2[i]

    def stats(self, name: str) -> Tuple[int, np.ndarray, np.ndarray]:
        """``(count, mean, population variance)`` of one class; ``"all"``
        is the two sexes together."""
        parts = ("sex0", "sex1") if name == "all" else (name,)
        n = sum(self.n[c] for c in parts)
        s1 = sum(self.s1[c] for c in parts)
        s2 = sum(self.s2[c] for c in parts)
        mean = s1 / n
        var = s2 / n - mean * mean
        return n, mean.reshape(self.shape), var.reshape(self.shape)


def check_answer(label: str, mean, var: Dict, ref: CohortReference,
                 name: str) -> None:
    """Compare one fused (mean, variance) answer with the reference;
    raises AssertionError outside tolerance."""
    n, rmean, rvar = ref.stats(name)
    mean = np.asarray(mean, np.float64)
    got_var = np.asarray(var["var"], np.float64)
    count = float(np.asarray(var["count"]))
    assert mean.shape == rmean.shape, (label, mean.shape, rmean.shape)
    assert np.isfinite(mean).all() and np.isfinite(got_var).all(), label
    assert count == n, f"{label}: count {count} != reference {n}"
    mean_err = float(np.abs(mean - rmean).max())
    var_err = float(np.abs(got_var - rvar).max())
    log(f"{label}: n={n} max|mean-ref|={mean_err:.3e} (tol {MEAN_TOL:g}) "
        f"max|var-ref|={var_err:.3e} (tol {VAR_TOL:g})")
    assert mean_err <= MEAN_TOL, f"{label}: mean error {mean_err}"
    assert var_err <= VAR_TOL, f"{label}: variance error {var_err}"


# ----------------------------------------------------------------------
# session, plans, queries
# ----------------------------------------------------------------------

def open_session(table, devices: Sequence, fold_interpret: bool = False,
                 plan_cache_cap: int = 64) -> GridSession:
    """A session whose data mesh is exactly ``devices`` (never the default
    every-visible-device mesh)."""
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    return GridSession(table, mesh=mesh, fold_impl="pallas",
                       fold_interpret=fold_interpret,
                       plan_cache_cap=plan_cache_cap)


def cohort_plans(session: GridSession):
    base = session.scan().select("img:data")
    lo, hi, sex = SUBSET

    def fused(q):
        return q.map(MeanProgram()).map(VarianceProgram()).reduce()

    return {
        "cohort": fused(base),
        "by_sex": fused(base.group_by("idx:sex")),
        "subset": fused(base.where(age_sex_predicate(lo, hi, sex),
                                   ["age", "sex"])),
    }


def timed_query(fe: GridFrontend, plan):
    t0 = time.perf_counter()
    res, rep = fe.query(plan, timeout=QUERY_TIMEOUT_S)
    jax.block_until_ready(jax.tree.leaves(
        res.values if hasattr(res, "values") else res))
    return res, rep, time.perf_counter() - t0


def describe_report(label: str, rep, wall: float) -> None:
    q = rep.query
    log(f"{label}: wall={wall:.3f}s rows_folded={q.rows_folded} "
        f"partials={q.partials_total} reused={q.partials_reused} "
        f"blocks_transferred={q.blocks_transferred} "
        f"gather_path={q.gather_path} merge_path={q.merge_path or '-'} "
        f"result_cache_hit={rep.plan_cache_hit}")


def check_grouped(label: str, res, ref: CohortReference) -> None:
    keys = [int(k) for k in res.keys]
    assert keys == [0, 1], f"{label}: group keys {keys}"
    mean, var = res.values
    for g, k in enumerate(keys):
        check_answer(f"{label}[sex={k}]", np.asarray(mean)[g],
                     {n: np.asarray(a)[g] for n, a in var.items()},
                     ref, f"sex{k}")


def upload_batch(table, n_rows: int, seed: int):
    """New full-width volumes keyed into the table's smallest region."""
    counts = table.region_row_counts()
    region = min(table.regions, key=lambda r: (counts[r.rid], r.rid))
    first = bytes(table.keys[table.region_rows(region).start])
    keys = [first + b"u%02d" % i for i in range(n_rows)]
    rng = np.random.default_rng(seed)
    shape = table.column_spec("img", "data").shape
    ages = rng.uniform(4.0, 98.0, n_rows).astype(np.float32)
    sexes = (np.arange(n_rows) % 2).astype(np.int8)
    data = rng.standard_normal((n_rows,) + tuple(shape), dtype=np.float32)
    data += (ages / np.float32(100.0)).reshape((n_rows,) + (1,) * len(shape))
    return keys, {"img": {"data": data},
                  "idx": {"size": rng.integers(6_000_000, 20_000_001, n_rows),
                          "age": ages, "sex": sexes}}


def peak_hbm(devices: Sequence) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def describe_layout(session: GridSession) -> Dict[str, object]:
    table = session.table
    counts = table.region_row_counts()
    rows = [counts[r.rid] for r in table.regions]
    spec = table.column_spec("img", "data")
    buckets = [session.engine.bucket_rows(r) for r in rows]
    out = {
        "subjects": table.num_rows,
        "volume": "x".join(map(str, spec.shape)),
        "payload_bytes": table.num_rows * spec.row_nbytes,
        "regions": len(rows),
        "rows_per_region": rows,
        "rows_per_block": buckets,
        "block_bytes_max": max(buckets) * spec.row_nbytes,
    }
    log(f"table: {out['subjects']} subjects x {out['volume']} float32 = "
        f"{out['payload_bytes'] / 1e9:.3f} GB payload; {out['regions']} "
        f"regions, rows per region {rows}, rows per committed block "
        f"{buckets} (largest block {out['block_bytes_max'] / 1e9:.3f} GB)")
    return out


def single_chip_phase(table, ref: CohortReference, devices: Sequence,
                      fold_interpret: bool = False) -> Dict[str, object]:
    """The five queries through a frontend on one device; raises on any
    wrong answer, fallback fold, or unexpected refold."""
    obs: Dict[str, object] = {}
    t0 = time.perf_counter()
    session = open_session(table, devices, fold_interpret)
    obs["session_open_s"] = time.perf_counter() - t0
    obs.update(describe_layout(session))
    plans = cohort_plans(session)
    n_regions = len(table.regions)
    # coalescing off: every query executes and reports its own work
    with GridFrontend(session, workers=2, coalesce=False) as fe:
        res, rep, wall = timed_query(fe, plans["cohort"])
        describe_report("q1 cohort", rep, wall)
        mean, var = res
        check_answer("q1 cohort", mean, var, ref, "all")
        assert rep.query.rows_folded == table.num_rows, rep.query
        obs["q1_cohort_s"] = wall

        res, rep, wall = timed_query(fe, plans["by_sex"])
        describe_report("q2 by_sex", rep, wall)
        check_grouped("q2 by_sex", res, ref)
        obs["q2_by_sex_s"] = wall

        res, rep, wall = timed_query(fe, plans["subset"])
        describe_report("q3 subset", rep, wall)
        mean, var = res
        check_answer("q3 subset", mean, var, ref, "subset")
        assert rep.query.gather_path == "blocks", rep.query
        obs["q3_subset_s"] = wall

        res, rep, wall = timed_query(fe, plans["cohort"])
        describe_report("q4 repeat", rep, wall)
        assert rep.query.rows_folded == 0, rep.query
        mean, var = res
        check_answer("q4 repeat", mean, var, ref, "all")
        obs["q4_repeat_s"] = wall
        obs["q4_repeat_rows_folded"] = rep.query.rows_folded

        keys, batch = upload_batch(table, UPLOAD_ROWS, seed=SEED + 1)
        t0 = time.perf_counter()
        assert fe.upload(keys, batch) == UPLOAD_ROWS
        obs["upload_s"] = time.perf_counter() - t0
        img, idx = batch["img"], batch["idx"]
        ref.add_rows(img["data"], idx["age"], idx["sex"])
        dirty = table.regions.regions_containing(keys)
        counts = table.region_row_counts()
        res, rep, wall = timed_query(fe, plans["cohort"])
        describe_report("q5 after upload", rep, wall)
        mean, var = res
        check_answer("q5 after upload", mean, var, ref, "all")
        refolded = rep.query.partials_total - rep.query.partials_reused
        assert refolded == len(dirty), (refolded, dirty)
        assert rep.query.rows_folded == sum(counts[r] for r in dirty)
        assert len(table.regions) >= n_regions
        obs["q5_after_upload_s"] = wall
        obs["q5_regions_refolded"] = refolded

    counts = dict(session.engine.fold_path_counts)
    log(f"fold_path_counts={counts} "
        f"merge_path_counts={dict(session.engine.merge_path_counts)} "
        f"engine_compiles={session.engine.compile_count}")
    assert counts["xla"] == 0, f"a fold fell back to XLA: {counts}"
    assert counts["pallas"] > 0, counts
    obs["fold_path_counts"] = counts
    obs["device_block_bytes"] = session.blocks.stats.device_bytes
    session.close()
    return obs


def mesh_phase(table, ref: CohortReference, devices: Sequence,
               fold_interpret: bool = False) -> Dict[str, object]:
    """The per-sex query on a data mesh of ``devices``: psum tree merge,
    then the funnel merge over the same cached partials, then a rebalance
    and a repeat that must fold nothing."""
    assert len(devices) > 1, "the mesh phase needs several devices"
    obs: Dict[str, object] = {}
    # no result cache: each query re-merges the per-block partials, so the
    # funnel run and the post-rebalance repeat exercise a real merge
    session = open_session(table, devices, fold_interpret, plan_cache_cap=0)
    obs.update(describe_layout(session))
    owners = sorted(session.placement.alloc.values())
    log(f"mesh: {len(devices)} devices, region owners {owners}")
    plan = cohort_plans(session)["by_sex"]
    with GridFrontend(session, workers=2, coalesce=False) as fe:
        tree, rep, wall = timed_query(fe, plan)
        describe_report("m1 by_sex tree", rep, wall)
        assert rep.query.merge_path == "tree", rep.query
        check_grouped("m1 by_sex tree", tree, ref)
        obs["m1_tree_s"] = wall

        session.engine.merge_strategy = "funnel"
        funnel, rep, wall = timed_query(fe, plan)
        describe_report("m2 by_sex funnel", rep, wall)
        assert rep.query.merge_path == "funnel", rep.query
        assert rep.query.rows_folded == 0, rep.query
        check_grouped("m2 by_sex funnel", funnel, ref)
        diffs = [float(np.abs(np.asarray(a, np.float64)
                              - np.asarray(b, np.float64)).max())
                 for a, b in zip(jax.tree.leaves(tree.values),
                                 jax.tree.leaves(funnel.values))]
        log(f"tree vs funnel: max |difference| per leaf {diffs}")
        assert max(diffs) <= MEAN_TOL, diffs
        obs["m2_funnel_s"] = wall
        obs["tree_vs_funnel_max_diff"] = max(diffs)
        session.engine.merge_strategy = "auto"

        # device 0 claims three times the power: the balancer must move
        # regions onto it
        nodes = [NodeSpec(0, cores=3)] + [NodeSpec(i)
                                          for i in range(1, len(devices))]
        moved = fe.rebalance(nodes=nodes)
        log(f"rebalance moved regions {sorted(moved)}")
        assert moved, "rebalance moved nothing"
        res, rep, wall = timed_query(fe, plan)
        describe_report("m3 by_sex after rebalance", rep, wall)
        assert rep.query.rows_folded == 0, rep.query
        assert rep.query.merge_path == "tree", rep.query
        check_grouped("m3 by_sex after rebalance", res, ref)
        obs["m3_after_rebalance_s"] = wall
        obs["regions_moved"] = len(moved)
    assert session.engine.fold_path_counts["xla"] == 0, \
        session.engine.fold_path_counts
    obs["fold_path_counts"] = dict(session.engine.fold_path_counts)
    session.close()
    return obs


# ----------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------

def _compile_seconds():
    """Running total of backend compile time, from JAX's own events."""
    total = [0.0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    return total


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip phases; 4: only the four-chip "
                         "mesh phase (tree vs funnel merge, rebalance)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform!r} devices", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    cache = use_compile_cache(REPO)
    compiled = _compile_seconds()
    use = devices[:args.chips]
    log(f"device: {devices[0].device_kind} x{len(devices)} visible, "
        f"using {len(use)}; jax {jax.__version__}; compile cache {cache}")

    t0 = time.perf_counter()
    table = build_cohort()
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = CohortReference.of_table(table)
    log(f"set-up: cohort built in {build_s:.1f}s, float64 reference in "
        f"{time.perf_counter() - t0:.1f}s")

    phase = mesh_phase if args.chips > 1 else single_chip_phase
    t0 = time.perf_counter()
    obs = phase(table, ref, use)
    obs["phase_s"] = time.perf_counter() - t0
    obs["compile_s"] = compiled[0]
    obs["peak_hbm_bytes"] = peak_hbm(use)
    peak = obs["peak_hbm_bytes"]
    log(f"phase wall {obs['phase_s']:.1f}s, of which backend compile "
        f"{obs['compile_s']:.1f}s; peak HBM "
        + ("not reported" if peak is None else f"{peak / 1e9:.3f} GB"))
    log("observations " + json.dumps(obs, sort_keys=True, default=str))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
