"""Block-granular fold engine: per-block partial caching, fused-program CSE,
the adaptive compact gather, BlockStore-routed retrieves, and the Pallas
map phase.

The PR acceptance oracles live here and in test_grid/test_differential:
a repeat ``.stats()`` on an unchanged epoch folds zero payload rows; a
single-region mutation re-folds only that region's blocks; a CSE'd fused
mean+variance+moments computes each shared accumulator once per chunk
(FLOP-counted against the naive fusion) while matching independently-run
member programs within float tolerance.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.grid import GridSession
from repro.core.mapreduce import MapReduceEngine
from repro.core.query import age_sex_predicate
from repro.core.regions import HierarchicalSplitPolicy
from repro.core.stats import (
    CountProgram,
    FusedProgram,
    HistogramProgram,
    MeanProgram,
    MomentsProgram,
    VarianceProgram,
)
from repro.core.table import ColumnSpec, make_mip_table
from repro.utils import make_mesh

PAYLOAD = (3, 4)


def make_table(groups=("a", "b", "c", "d", "e"), per=8, seed=0):
    rng = np.random.default_rng(seed)
    t = make_mip_table(
        payload_shape=PAYLOAD,
        extra_index_columns=[ColumnSpec("age", (), np.float32),
                             ColumnSpec("sex", (), np.int8)],
        split_policy=HierarchicalSplitPolicy(max_region_bytes=10**18),
        presplit_keys=list(groups)[1:],
    )
    keys = [f"{g}{i:04d}" for g in groups for i in range(per)]
    n = len(keys)
    t.upload(keys, {
        "img": {"data": rng.normal(size=(n,) + PAYLOAD).astype(np.float32)},
        "idx": {"size": rng.integers(6_000_000, 20_000_001, n),
                "age": rng.uniform(4, 80, n).astype(np.float32),
                "sex": rng.integers(0, 2, n).astype(np.int8)}})
    return t


# ----------------------------------------------------------------------
# engine units: per-block folds merge to the layout-at-a-time answer
# ----------------------------------------------------------------------

class TestBlockFoldEngine:
    @pytest.mark.parametrize("program,eta", [
        (MeanProgram(), 4),
        (VarianceProgram(), 3),
        (MomentsProgram(), 7),
        (HistogramProgram(lo=-4.0, hi=4.0, bins=16), 5),
    ])
    def test_blockwise_equals_monolithic(self, program, eta):
        rng = np.random.default_rng(1)
        mesh = make_mesh((jax.device_count(),), ("data",))
        eng = MapReduceEngine(mesh)
        blocks = [rng.normal(size=(r,) + PAYLOAD).astype(np.float32)
                  for r in (5, 9, 1, 12)]
        partials = [eng.fold_block(program, jnp.asarray(b), None, eta,
                                   PAYLOAD, np.float32) for b in blocks]
        got = eng.merge_finalize(program, partials, PAYLOAD, np.float32)

        data = np.concatenate(blocks)
        cap = -(-len(data) // eta) * eta
        vals = np.zeros((1, cap) + PAYLOAD, np.float32)
        vals[0, :len(data)] = data
        valid = np.zeros((1, cap), bool)
        valid[0, :len(data)] = True
        # single-shard reference fold (mesh-independent ground truth)
        ref, _ = MapReduceEngine(make_mesh((1,), ("data",))).run(
            program, vals, valid, eta)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4),
            got, ref)

    def test_masked_fold_skips_rows(self):
        rng = np.random.default_rng(2)
        eng = MapReduceEngine(make_mesh((1,), ("data",)))
        block = rng.normal(size=(10,) + PAYLOAD).astype(np.float32)
        mask = np.zeros(10, bool)
        mask[[1, 4, 7]] = True
        p = eng.fold_block(MeanProgram(), jnp.asarray(block),
                           jnp.asarray(mask), 4, PAYLOAD, np.float32)
        got = eng.merge_finalize(MeanProgram(), [p], PAYLOAD, np.float32)
        np.testing.assert_allclose(np.asarray(got), block[mask].mean(0),
                                   atol=1e-5)

    def test_zero_partials_finalize_identity(self):
        eng = MapReduceEngine(make_mesh((1,), ("data",)))
        got = eng.merge_finalize(MeanProgram(), [], PAYLOAD, np.float32)
        assert np.all(np.asarray(got) == 0)  # sum 0 / max(count,1)

    def test_fold_cost_reports_flops(self):
        eng = MapReduceEngine(make_mesh((1,), ("data",)))
        cost = eng.fold_cost(MeanProgram(), 16, PAYLOAD, jnp.float32, 4)
        assert cost["flops"] >= 0 and cost["bytes"] >= 0


# ----------------------------------------------------------------------
# partial cache: content-addressed sharing across plans and epochs
# ----------------------------------------------------------------------

class TestPartialCache:
    def test_range_covering_whole_regions_shares_full_partials(self):
        t = make_table()
        s = GridSession(t, default_eta=4)
        s.run(MeanProgram())                       # full partials for a..e
        r = s.scan(start="a", stop="c").map(MeanProgram()).stats()
        q = r.query
        # regions a and b are fully covered: mask sig "full" matches the
        # full-table partials — nothing re-folds, no blocks touched
        assert q.partials_total == 2
        assert q.partials_reused == 2 and q.rows_folded == 0, q

    def test_same_selection_different_predicate_objects_share(self):
        t = make_table(per=16, seed=3)
        s = GridSession(t, default_eta=4, compact_gather_threshold=0.0)
        p1 = age_sex_predicate(20, 40, 1)
        p2 = age_sex_predicate(20, 40, 1)          # distinct object, same rows
        r1 = (s.scan(prefix="b").where(p1, ["age", "sex"])
              .map(MeanProgram()).stats())
        r2 = (s.scan(prefix="b").where(p2, ["age", "sex"])
              .map(MeanProgram()).stats())
        # mask signatures are content hashes, not object identities
        assert r2.plan_cache_hit
        assert r2.query.rows_folded == 0
        assert r1.query.rows_selected == r2.query.rows_selected

    def test_partials_survive_block_cache_eviction(self):
        t = make_table()                            # 5 regions
        s = GridSession(t, default_eta=4, block_cache_cap=2)
        s.run(MeanProgram())
        assert s.blocks.evictions >= 3
        _, r = s.run(MeanProgram())
        # evicted BLOCKS don't matter: the partials carry the repeat
        assert r.plan_cache_hit and r.query.rows_folded == 0

    def test_partial_cache_eviction_refolds_losslessly(self):
        t = make_table()
        s = GridSession(t, default_eta=4, partial_cache_cap=2)
        res, _ = s.run(MeanProgram())
        _, r2 = s.run(MeanProgram())                # result cache still hits
        assert r2.plan_cache_hit
        s._results.clear()                          # force the partial path
        res3, r3 = s.run(MeanProgram())
        assert r3.query.rows_folded > 0             # some partials re-folded
        np.testing.assert_allclose(np.asarray(res3), np.asarray(res),
                                   atol=1e-5)

    def test_distinct_programs_keep_distinct_partials(self):
        t = make_table()
        s = GridSession(t, default_eta=4)
        s.run(MeanProgram())
        _, r = s.run(VarianceProgram())
        q = r.query
        assert q.partials_reused == 0 and q.rows_folded > 0
        # but the BLOCKS are shared: no re-gather, no re-transfer
        assert q.gather_count == 0
        assert q.blocks_reused == q.blocks_total


# ----------------------------------------------------------------------
# adaptive compact gather (cold low-selectivity one-shots)
# ----------------------------------------------------------------------

class TestCompactGather:
    def pred_few(self):
        # selects exactly the rows with sex == 1 and age in a sliver
        return age_sex_predicate(None, 6.0, None)

    def test_cold_selective_scan_goes_compact(self):
        t = make_table(per=32, seed=5)
        s = GridSession(t, default_eta=4, compact_gather_threshold=0.2)
        pred = self.pred_few()
        mask = pred({"age": t.column("idx", "age"),
                     "sex": t.column("idx", "sex")})
        if not mask.any():
            pytest.skip("seed selected nothing")
        res, rep = s.run_where(pred, MeanProgram(), ["age", "sex"])
        q = rep.query
        assert q.gather_path == "compact", q
        assert q.partials_total == 0 and q.blocks_total == 0, q
        assert q.rows_folded == int(mask.sum()), q
        # only the selected rows crossed to the device
        row_nbytes = t.column_spec("img", "data").row_nbytes
        assert q.payload_bytes_transferred == int(mask.sum()) * row_nbytes
        np.testing.assert_allclose(
            np.asarray(res), t.column("img", "data")[mask].mean(0),
            atol=1e-5)
        # one-shot: nothing entered the block or partial caches
        assert len(s.blocks) == 0 and s.blocks.partial_count == 0
        assert s.metrics.compact_scans == 1
        # ...but the finalized result is memoized: an identical repeat
        # (fresh plan object) pays neither gather nor fold
        res2, rep2 = s.run_where(pred, MeanProgram(), ["age", "sex"])
        assert rep2.plan_cache_hit
        assert rep2.query.gather_path == "compact"
        assert rep2.query.rows_folded == 0
        rep2.query.check_partial_invariant()
        np.testing.assert_array_equal(np.asarray(res2), np.asarray(res))
        assert s.metrics.compact_scans == 1         # no second gather pass

    def test_has_partials_index_tracks_versions(self):
        t = make_table()
        s = GridSession(t, default_eta=4)
        s.run(MeanProgram())
        rid = t.regions.region_for(b"a0000").rid
        assert s.blocks.has_partials(rid)
        s.remove(rowkey=b"a0000")                   # version bump: stale now
        assert not s.blocks.has_partials(rid)
        s.run(MeanProgram())                        # re-folds current version
        assert s.blocks.has_partials(rid)
        s.blocks.clear_partials()
        assert not s.blocks.has_partials(rid)

    def test_resident_blocks_override_compact(self):
        t = make_table(per=32, seed=5)
        s = GridSession(t, default_eta=4, compact_gather_threshold=0.2)
        s.run(MeanProgram())                        # blocks now resident
        res, rep = s.run_where(self.pred_few(), MeanProgram(),
                               ["age", "sex"])
        assert rep.query.gather_path == "blocks"    # reuse beats cold cost
        assert rep.query.gather_count == 0          # ...and pays off

    def test_threshold_zero_disables_compact(self):
        t = make_table(per=32, seed=5)
        s = GridSession(t, default_eta=4, compact_gather_threshold=0.0)
        _, rep = s.run_where(self.pred_few(), MeanProgram(), ["age", "sex"])
        assert rep.query.gather_path == "blocks"

    def test_threshold_exposed_on_session(self):
        s = GridSession(make_table(), compact_gather_threshold=0.25)
        assert s.compact_gather_threshold == 0.25


# ----------------------------------------------------------------------
# retrieves route through the BlockStore
# ----------------------------------------------------------------------

class TestRetrieveThroughBlocks:
    def test_second_retrieve_rereads_nothing(self):
        t = make_table()
        s = GridSession(t, default_eta=4)
        (k1, c1), r1 = s.scan(prefix="b").select("img:data").collect()
        assert r1.query.gather_path == "retrieve"
        assert r1.query.gather_count == 1           # cold: one region read
        (k2, c2), r2 = s.scan(prefix="b").select("img:data").collect()
        assert r2.query.gather_count == 0           # host block reused
        assert r2.query.blocks_reused == r2.query.blocks_total == 1
        np.testing.assert_array_equal(c1["img:data"], c2["img:data"])
        np.testing.assert_array_equal(c1["img:data"],
                                      t.column("img", "data")[8:16])

    def test_fold_after_retrieve_shares_the_gather(self):
        t = make_table()
        s = GridSession(t, default_eta=4)
        s.scan(prefix="b").select("img:data").collect()
        _, rep = s.scan(prefix="b").map(MeanProgram()).collect()
        # the fold commits the retrieve's host block to its device —
        # zero table re-reads
        assert rep.query.gather_count == 0, rep.query

    def test_multi_column_retrieve(self):
        t = make_table()
        s = GridSession(t, default_eta=4)
        (keys, cols), rep = (s.scan(prefix="c")
                             .select("img:data", "idx:age").collect())
        np.testing.assert_array_equal(cols["img:data"],
                                      t.column("img", "data")[16:24])
        np.testing.assert_array_equal(cols["idx:age"],
                                      t.column("idx", "age")[16:24])
        rep.query.check_block_invariant()


# ----------------------------------------------------------------------
# fused-program CSE: equality property + FLOP accounting
# ----------------------------------------------------------------------

CSE_MEMBERS = (MeanProgram(), VarianceProgram(), MomentsProgram())


class TestFusedCSE:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_cse_matches_independent_runs(self, seed):
        """Property: the CSE'd fusion equals each member run standalone
        (up to float associativity), across random tables/etas."""
        rng = np.random.default_rng(seed)
        t = make_table(per=int(rng.integers(3, 12)), seed=seed)
        eta = int(rng.integers(1, 9))
        s = GridSession(t, default_eta=eta)
        q = s.scan()
        for p in CSE_MEMBERS + (HistogramProgram(lo=-4, hi=4, bins=8),
                                CountProgram()):
            q = q.map(p)
        fused_res, _ = q.collect()
        for p, got in zip(CSE_MEMBERS + (HistogramProgram(lo=-4, hi=4,
                                                          bins=8),
                                         CountProgram()), fused_res):
            solo = GridSession(t, default_eta=eta)
            want, _ = solo.run(p)
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-3),
                got, want)

    def test_cse_and_naive_fusion_agree(self):
        t = make_table(per=10, seed=7)
        data = t.column("img", "data")
        s = GridSession(t, default_eta=4)
        (m1, v1, mo1), _ = (s.scan().map(MeanProgram())
                            .map(VarianceProgram()).map(MomentsProgram())
                            .collect())
        np.testing.assert_allclose(np.asarray(m1), data.mean(0), atol=1e-5)
        np.testing.assert_allclose(np.asarray(v1["var"]), data.var(0),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(mo1["var"]), data.var(0),
                                   atol=1e-4)

    def test_cse_fold_costs_fewer_flops_than_naive(self):
        """The accumulators really are computed once: XLA's own CSE cannot
        recover the naive fusion's duplicated folds."""
        eng = MapReduceEngine(make_mesh((1,), ("data",)))
        cse = FusedProgram(CSE_MEMBERS)
        naive = FusedProgram(CSE_MEMBERS, cse=False)
        fc = eng.fold_cost(cse, 64, PAYLOAD, jnp.float32, 8)
        fn = eng.fold_cost(naive, 64, PAYLOAD, jnp.float32, 8)
        if fc["flops"] == 0 or fn["flops"] == 0:
            pytest.skip("cost_analysis reports no flops on this backend")
        assert fc["flops"] < 0.9 * fn["flops"], (fc, fn)

    def test_cse_partial_is_single_accumulator_set(self):
        cse = FusedProgram(CSE_MEMBERS)
        zero = cse.zero(PAYLOAD, np.float32)
        # one float32 pool with count + s1..s4, and no private partials
        assert zero["private"] == ()
        (dt, pool), = ((k, v) for k, v in zero["shared"].items())
        assert set(pool) == {"count", "s1", "s2", "s3", "s4"}
        assert cse.additive

    def test_non_cse_members_keep_private_folds(self):
        fused = FusedProgram((MeanProgram(), CountProgram(),
                              HistogramProgram()))
        zero = fused.zero(PAYLOAD, np.float32)
        assert len(zero["private"]) == 2       # count (int32) + histogram
        res = fused.finalize(fused.map_chunk(
            jnp.ones((4,) + PAYLOAD), jnp.ones((4,), bool)))
        assert int(res[1]) == 4                # exact int32 count survives


# ----------------------------------------------------------------------
# Pallas map phase (opt-in impl="pallas")
# ----------------------------------------------------------------------

class TestPallasMapPhase:
    def test_mean_ref_vs_pallas_equivalence(self):
        t = make_table(per=10, seed=2)
        s = GridSession(t, default_eta=4, fold_interpret=True)
        ref, _ = s.run(MeanProgram(), impl="ref")
        pal, rep = s.run(MeanProgram(), impl="pallas")
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=1e-5)
        assert rep.query.partials_total == len(t.regions)

    def test_variance_ref_vs_pallas_equivalence(self):
        t = make_table(per=10, seed=2)
        s = GridSession(t, default_eta=4, fold_interpret=True)
        ref, _ = s.run(VarianceProgram())
        pal, _ = s.run(VarianceProgram(), impl="pallas")
        np.testing.assert_allclose(np.asarray(pal["mean"]),
                                   np.asarray(ref["mean"]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(pal["var"]),
                                   np.asarray(ref["var"]), atol=1e-4)
        np.testing.assert_allclose(float(pal["count"]), float(ref["count"]))

    def test_pallas_partials_cache_separately_from_ref(self):
        t = make_table(per=10, seed=2)
        s = GridSession(t, default_eta=4, fold_interpret=True)
        s.run(MeanProgram())
        _, rep = s.run(MeanProgram(), impl="pallas")
        assert rep.query.partials_reused == 0      # kernel identity differs
        _, rep2 = s.run(MeanProgram(), impl="pallas")
        assert rep2.query.rows_folded == 0         # but caches like any other

    def test_unsupported_program_raises(self):
        from repro.kernels.streaming_stats.ops import kernel_map_program
        with pytest.raises(ValueError):
            kernel_map_program(HistogramProgram())
        with pytest.raises(ValueError):
            kernel_map_program(MeanProgram(), impl="cuda")

    def test_grouped_fold_ref_vs_pallas_equivalence(self):
        """The fused fold kernel (session-level ``fold_impl="pallas"``)
        extends the ref-vs-pallas equivalence to GROUPED folds — the
        map-phase ``impl="pallas"`` twin never covered those."""
        def grouped(s):
            return (s.scan().select("img:data").group_by("idx:sex")
                    .map(MeanProgram()).map(VarianceProgram())
                    .map(MomentsProgram()).reduce().collect())
        ref, _ = grouped(GridSession(make_table(per=10, seed=2),
                                     default_eta=4, fold_impl="xla"))
        s = GridSession(make_table(per=10, seed=2), default_eta=4,
                        fold_impl="pallas", fold_interpret=True)
        pal, _ = grouped(s)
        assert s.engine.fold_path_counts["pallas"] > 0
        assert list(pal.keys) == list(ref.keys)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float64), np.asarray(b, np.float64),
                rtol=1e-4, atol=1e-3),
            list(pal.values), list(ref.values))
