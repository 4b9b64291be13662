"""Fused Pallas fold kernel: one HBM pass per block for the grouped CSE
shared-accumulator pool.

The PR acceptance oracles live here: the kernel matches the float64 NumPy
oracle within fp32 accumulation tolerance across dtypes (bf16/f32/i32
rows), group counts {1, 7, 64} and ragged row counts hitting the pow2
padding; NaN/Inf in masked-off rows never poison accumulators; the
engine's pallas fold path is bitwise-compatible (within fp32 tolerance)
with the XLA fold for grouped AND ungrouped CSE folds; ineligible fold
signatures fall back to XLA; pallas fold executables stay keyed on the
pow2 row bucket and are chunk-free (η never enters the key); and the gid
block cache makes dirty-region re-folds skip re-densifying group ids.

Runs entirely in Pallas interpret mode on CPU, asked for explicitly
(``fold_interpret=True`` / the op's ``interpret=True``).
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # CI installs hypothesis; bare containers skip
    HAVE_HYPOTHESIS = False

import jax
import jax.numpy as jnp

from repro.core.grid import GridSession
from repro.core.mapreduce import MapReduceEngine
from repro.core.regions import HierarchicalSplitPolicy
from repro.core.stats import (
    CountProgram,
    FusedProgram,
    GroupedProgram,
    HistogramProgram,
    MeanProgram,
    MomentsProgram,
    VarianceProgram,
)
from repro.core.table import ColumnSpec, make_mip_table
from repro.kernels.fused_fold import (
    fused_fold,
    fused_fold_numpy,
    kernel_flops,
    kernel_hbm_bytes,
    max_groups_for_vmem,
)
from repro.kernels.fused_fold.kernel import ACC_ORDER, rowsum_tiles
from repro.utils import make_mesh

rng = np.random.default_rng(421)

PAYLOAD = (3, 4)
CSE_MEMBERS = (MeanProgram(), VarianceProgram(), MomentsProgram())


def assert_pool_close(got, want, rtol=1e-4, atol=1e-3):
    assert set(got) == set(want)
    for n in want:
        np.testing.assert_allclose(np.asarray(got[n], np.float64),
                                   np.asarray(want[n], np.float64),
                                   rtol=rtol, atol=atol, err_msg=n)


# ----------------------------------------------------------------------
# kernel vs the float64 NumPy oracle
# ----------------------------------------------------------------------

class TestKernelVsOracle:
    @pytest.mark.parametrize("G", [1, 7, 64])
    @pytest.mark.parametrize("R,shape", [
        (1, (8,)), (13, (5,)), (64, (12, 11)), (300, (130,)),
    ])
    def test_f32_grouped_matches_oracle(self, R, shape, G):
        x = rng.normal(size=(R,) + shape).astype(np.float32)
        m = rng.random(R) > 0.25
        g = rng.integers(0, G, R).astype(np.int32)
        got = fused_fold(jnp.asarray(x), jnp.asarray(m), jnp.asarray(g),
                         num_groups=G, interpret=True)
        want = fused_fold_numpy(x, m, g, num_groups=G)
        assert got["count"].shape == (G,)
        assert got["s1"].shape == (G,) + shape
        assert_pool_close(got, want)

    @pytest.mark.parametrize("G", [1, 7])
    def test_bf16_rows(self, G):
        x32 = rng.normal(size=(50, 24)).astype(np.float32)
        x = jnp.asarray(x32).astype(jnp.bfloat16)
        m = rng.random(50) > 0.3
        g = rng.integers(0, G, 50).astype(np.int32)
        got = fused_fold(x, jnp.asarray(m), jnp.asarray(g), num_groups=G, interpret=True)
        want = fused_fold_numpy(np.asarray(x, np.float32), m, g,
                                num_groups=G)
        # bf16 rows: ~3 significand digits; s4 amplifies to ~1e-1
        assert_pool_close(got, want, rtol=5e-2, atol=2e-1)
        np.testing.assert_array_equal(np.asarray(got["count"]),
                                      want["count"])

    @pytest.mark.parametrize("G", [1, 7])
    def test_i32_rows(self, G):
        x = rng.integers(-9, 10, size=(40, 16)).astype(np.int32)
        m = rng.random(40) > 0.5
        g = rng.integers(0, G, 40).astype(np.int32)
        got = fused_fold(jnp.asarray(x), jnp.asarray(m), jnp.asarray(g),
                         num_groups=G, interpret=True)
        # small ints: fp32 accumulation is exact
        assert_pool_close(got, fused_fold_numpy(x, m, g, num_groups=G),
                          rtol=0, atol=0)

    def test_defaults_are_ungrouped_unmasked(self):
        x = rng.normal(size=(20, 8)).astype(np.float32)
        got = fused_fold(jnp.asarray(x), interpret=True)
        assert_pool_close(got, fused_fold_numpy(x))

    def test_accumulator_subset(self):
        x = rng.normal(size=(33, 9)).astype(np.float32)
        m = rng.random(33) > 0.4
        got = fused_fold(jnp.asarray(x), jnp.asarray(m),
                         names=("count", "s1", "s2"), interpret=True)
        assert set(got) == {"count", "s1", "s2"}
        assert_pool_close(
            got, fused_fold_numpy(x, m, names=("count", "s1", "s2")))

    def test_empty_groups_stay_zero(self):
        x = rng.normal(size=(16, 4)).astype(np.float32)
        g = np.zeros(16, np.int32)          # everything lands in group 0
        got = fused_fold(jnp.asarray(x), None, jnp.asarray(g), num_groups=5, interpret=True)
        np.testing.assert_array_equal(np.asarray(got["count"])[1:], 0)
        np.testing.assert_array_equal(np.asarray(got["s2"])[1:], 0)

    def _check_ragged(self, R, F, G, seed):
        """Ragged R/F exercise the kernel's ragged edge tiles: rows past
        the block's end are masked off by row index, columns past it only
        reach output columns that are never written, padded groups
        receive no rows — the oracle never sees any of it."""
        r = np.random.default_rng(seed)
        x = r.normal(size=(R, F)).astype(np.float32)
        m = r.random(R) > 0.5
        g = r.integers(0, G, R).astype(np.int32)
        got = fused_fold(jnp.asarray(x), jnp.asarray(m), jnp.asarray(g),
                         num_groups=G, interpret=True)
        want = fused_fold_numpy(x, m, g, num_groups=G)
        assert_pool_close(got, want, rtol=1e-3, atol=1e-2)
        np.testing.assert_array_equal(np.asarray(got["count"]),
                                      want["count"])

    @pytest.mark.parametrize("R,F,G,seed", [
        (13, 5, 1, 0), (255, 129, 7, 1), (257, 3, 64, 2), (9, 200, 7, 3),
    ])
    def test_ragged_shapes_fixed(self, R, F, G, seed):
        self._check_ragged(R, F, G, seed)

    if HAVE_HYPOTHESIS:
        @given(
            R=st.integers(1, 300),
            F=st.integers(1, 200),
            G=st.sampled_from([1, 7, 64]),
            seed=st.integers(0, 2**31 - 1),
        )
        @settings(max_examples=25, deadline=None)
        def test_property_ragged_shapes(self, R, F, G, seed):
            self._check_ragged(R, F, G, seed)

    def test_nan_inf_in_masked_rows_never_poison(self):
        """Regression: masked rows are ZEROED BEFORE the power raises.
        A masked row full of NaN/Inf must leave every accumulator finite
        and equal to the fold of the valid rows alone (0·NaN = NaN, so a
        multiply-by-mask kernel would fail this)."""
        x = rng.normal(size=(24, 10)).astype(np.float32)
        m = np.ones(24, bool)
        m[[3, 11, 17]] = False
        x[3] = np.nan
        x[11] = np.inf
        x[17, ::2] = -np.inf
        g = rng.integers(0, 3, 24).astype(np.int32)
        got = fused_fold(jnp.asarray(x), jnp.asarray(m), jnp.asarray(g),
                         num_groups=3, interpret=True)
        for n, a in got.items():
            assert bool(jnp.isfinite(a).all()), n
        assert_pool_close(got, fused_fold_numpy(x, m, g, num_groups=3))

    def test_all_masked(self):
        x = rng.normal(size=(32, 16)).astype(np.float32)
        got = fused_fold(jnp.asarray(x), jnp.asarray(np.zeros(32, bool)), interpret=True)
        for a in got.values():
            np.testing.assert_array_equal(np.asarray(a), 0)


# ----------------------------------------------------------------------
# the G = 1 schedule: VPU row sum over wide feature tiles, [1, F] pool
# ----------------------------------------------------------------------

WIDE_F = 40_000                # several 1024-lane tiles, ragged last one


class TestRowSumSchedule:
    @pytest.mark.parametrize("real,names,block_features", [
        (26, ("count", "s1"), 1024),
        (30, ("count", "s1", "s2"), 1024),
        (32, ACC_ORDER, 1024),
        (26, ACC_ORDER, None),
        (32, ("count", "s1", "s2"), None),
    ])
    def test_pad_rows_masked_wide_ragged_tiles(self, real, names,
                                               block_features):
        """A 32-row block holding ``real`` rows, as the engine commits a
        region: the pad rows hold NaN/Inf and are masked off, F is not a
        multiple of 128 and spans several feature tiles."""
        r = np.random.default_rng(real)
        x = r.normal(size=(32, WIDE_F)).astype(np.float32)
        x[real:] = np.nan
        x[real:, ::3] = np.inf
        m = r.random(32) > 0.4
        m[real:] = False
        got = fused_fold(jnp.asarray(x), jnp.asarray(m), names=names,
                         block_features=block_features, interpret=True)
        want = fused_fold_numpy(x, m, names=names)
        assert got["s1"].shape == (1, WIDE_F)
        for a in got.values():
            assert bool(jnp.isfinite(a).all())
        assert_pool_close(got, want)
        np.testing.assert_array_equal(np.asarray(got["count"]),
                                      want["count"])

    @pytest.mark.parametrize("names", [("count", "s1"),
                                       ("count", "s1", "s2"), ACC_ORDER])
    def test_mask_none(self, names):
        x = rng.normal(size=(32, WIDE_F)).astype(np.float32)
        got = fused_fold(jnp.asarray(x), None, names=names,
                         block_features=2048, interpret=True)
        assert_pool_close(got, fused_fold_numpy(x, names=names))
        assert float(got["count"][0]) == 32

    @pytest.mark.parametrize("R,block_rows", [(300, 256), (40, 16)])
    def test_row_tiles_accumulate(self, R, block_rows):
        """More rows than one row tile: the pool accumulates across the
        row sweep, the ragged last tile's rows masked by their index."""
        x = rng.normal(size=(R, 700)).astype(np.float32)
        m = rng.random(R) > 0.3
        got = fused_fold(jnp.asarray(x), jnp.asarray(m),
                         block_rows=block_rows, interpret=True)
        want = fused_fold_numpy(x, m)
        assert_pool_close(got, want)
        np.testing.assert_array_equal(np.asarray(got["count"]),
                                      want["count"])

    @pytest.mark.parametrize("names", [("count", "s1"), ACC_ORDER])
    def test_nan_inf_in_masked_rows_never_poison(self, names):
        x = rng.normal(size=(24, 300)).astype(np.float32)
        m = np.ones(24, bool)
        m[[0, 11, 23]] = False
        x[0] = np.nan
        x[11] = np.inf
        x[23, ::2] = -np.inf
        got = fused_fold(jnp.asarray(x), jnp.asarray(m), names=names,
                         block_features=128, interpret=True)
        for n, a in got.items():
            assert bool(jnp.isfinite(a).all()), n
        assert_pool_close(got, fused_fold_numpy(x, m, names=names))

    @pytest.mark.parametrize("rows,features,itemsize,want", [
        (32, 7_221_032, 4, (32, 32768, 1024)),     # a 1 mm block: 4 MiB
        (32, 7_221_032, 2, (32, 65536, 1024)),     # bf16 rows: 4 MiB too
        (1, 7_221_032, 4, (1, 131072, 4096)),      # rows pad to 8
        (300, 4096, 4, (256, 4096, 128)),
        (13, 5, 4, (13, 5, 5)),                    # narrower than a chunk
        (32, WIDE_F, 4, (32, 32768, 1024)),     # 2 tiles, the last ragged
    ])
    def test_tiles_sized_by_bytes(self, rows, features, itemsize, want):
        assert rowsum_tiles(rows, features, itemsize) == want

    def test_explicit_tile_must_be_lane_aligned(self):
        with pytest.raises(ValueError):
            rowsum_tiles(32, WIDE_F, 4, block_features=1000)
        assert rowsum_tiles(32, WIDE_F, 4, block_features=1536) == (
            32, 1536, 768)


# ----------------------------------------------------------------------
# engine dispatch: eligibility, fallback, executable keying
# ----------------------------------------------------------------------

def interp_engine(**kw):
    return MapReduceEngine(make_mesh((1,), ("data",)),
                           fold_interpret=True, **kw)


class TestFoldPath:
    def test_cse_programs_take_pallas(self):
        eng = interp_engine()
        for p in CSE_MEMBERS + (FusedProgram(CSE_MEMBERS),
                                GroupedProgram(FusedProgram(CSE_MEMBERS),
                                               num_groups=5)):
            assert eng.fold_path(p, np.float32, 0) == "pallas", p

    def test_fallback_without_interpret_off_tpu(self):
        eng = MapReduceEngine(make_mesh((1,), ("data",)))
        if jax.default_backend() != "tpu":
            assert eng.fold_path(MeanProgram(), np.float32) == "xla"

    def test_fallback_when_forced_xla(self):
        eng = interp_engine(fold_impl="xla")
        assert eng.fold_path(MeanProgram(), np.float32) == "xla"

    def test_fallback_outside_the_pool(self):
        eng = interp_engine()
        # private members / non-pool accumulators have no kernel form
        assert eng.fold_path(HistogramProgram(), np.float32) == "xla"
        assert eng.fold_path(CountProgram(), np.float32) == "xla"
        assert eng.fold_path(
            FusedProgram(CSE_MEMBERS + (CountProgram(),)),
            np.float32) == "xla"
        # non-fp32 accumulation keeps the reference fold
        assert eng.fold_path(MeanProgram(acc_dtype=jnp.float64),
                             np.float32) == "xla"

    def test_fallback_complex_dtype(self):
        assert interp_engine().fold_path(
            MeanProgram(), np.complex64) == "xla"

    def test_fallback_above_vmem_group_budget(self):
        eng = interp_engine()
        cap = max_groups_for_vmem(("count", "s1"))
        assert cap > 0
        prog = GroupedProgram(MeanProgram(), num_groups=cap + 1)
        assert eng.fold_path(prog, np.float32, cap + 1) == "xla"
        assert eng.fold_path(prog, np.float32, cap) == "pallas"

    def test_unknown_fold_impl_rejected(self):
        with pytest.raises(ValueError):
            MapReduceEngine(make_mesh((1,), ("data",)), fold_impl="cuda")

    def test_pallas_executables_are_chunk_free_and_bucketed(self):
        """η never enters the pallas fold key, and distinct row counts in
        one pow2 bucket share the executable — only a bucket change (or a
        G change) compiles."""
        eng = interp_engine()
        p = MeanProgram()
        n0 = eng.compile_count

        def fold(rows, eta):
            blk = jnp.asarray(
                rng.normal(size=(rows,) + PAYLOAD).astype(np.float32))
            return eng.fold_block(p, blk, None, eta, PAYLOAD, np.float32)

        fold(33, 4)                      # bucket 64: compile
        fold(61, 7)                      # same bucket, other η + rows
        fold(40, 2)
        assert eng.compile_count == n0 + 1
        fold(100, 4)                     # bucket 128: one more
        assert eng.compile_count == n0 + 2
        assert eng.fold_path_counts["pallas"] == 4


# ----------------------------------------------------------------------
# engine differential: pallas fold ≡ xla fold (grouped and ungrouped)
# ----------------------------------------------------------------------

class TestEngineDifferential:
    PROGRAMS = [
        MeanProgram(),
        VarianceProgram(),
        MomentsProgram(),
        FusedProgram(CSE_MEMBERS),
        GroupedProgram(MeanProgram(), num_groups=5),
        GroupedProgram(FusedProgram(CSE_MEMBERS), num_groups=5),
    ]

    @pytest.mark.parametrize(
        "program", PROGRAMS, ids=lambda p: str(p.cache_key()[0]))
    def test_pallas_equals_xla(self, program):
        grouped = isinstance(program, GroupedProgram)
        G = program.num_groups if grouped else 0
        blocks = [rng.normal(size=(r,) + PAYLOAD).astype(np.float32)
                  for r in (5, 33, 1, 64)]
        masks = [rng.random(len(b)) > 0.3 for b in blocks]
        gids = [rng.integers(0, max(1, G), len(b)).astype(np.int32)
                for b in blocks]
        results = {}
        for impl in ("pallas", "xla"):
            eng = interp_engine(fold_impl=impl)
            ps = []
            for b, m, g in zip(blocks, masks, gids):
                assert eng.fold_path(program, np.float32, G) == impl
                ps.append(eng.fold_block(
                    program, jnp.asarray(b), jnp.asarray(m), 4,
                    PAYLOAD, np.float32,
                    gids=jnp.asarray(g) if grouped else None,
                    num_groups=G))
            results[impl] = eng.merge_finalize(program, ps, PAYLOAD,
                                               np.float32)
            assert eng.fold_path_counts[impl] == len(blocks)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float64), np.asarray(b, np.float64),
                rtol=1e-4, atol=1e-3),
            results["pallas"], results["xla"])


    @pytest.mark.parametrize("masked", [True, False])
    def test_one_group_grouped_program_takes_the_row_sum(self, masked):
        """A one-group ``GroupedProgram`` folds on the row-sum schedule
        (gid 0 for every row) and agrees with the XLA fold, pad rows of
        the pow2 bucket included."""
        program = GroupedProgram(FusedProgram(CSE_MEMBERS), num_groups=1)
        blocks = [rng.normal(size=(r,) + PAYLOAD).astype(np.float32)
                  for r in (26, 30, 32, 5)]
        masks = [rng.random(len(b)) > 0.3 if masked else None
                 for b in blocks]
        results = {}
        for impl in ("pallas", "xla"):
            eng = interp_engine(fold_impl=impl)
            assert eng.kernel_schedule(program, np.float32, 1) == (
                "rowsum" if impl == "pallas" else "")
            ps = [eng.fold_block(
                program, jnp.asarray(b),
                None if m is None else jnp.asarray(m), 4, PAYLOAD,
                np.float32, gids=jnp.zeros(len(b), jnp.int32), num_groups=1)
                for b, m in zip(blocks, masks)]
            results[impl] = eng.merge_finalize(program, ps, PAYLOAD,
                                               np.float32)
            assert eng.fold_path_counts[impl] == len(blocks)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float64), np.asarray(b, np.float64),
                rtol=1e-4, atol=1e-3),
            results["pallas"], results["xla"])

    def test_kernel_schedule_by_group_count(self):
        eng = interp_engine()
        fused = FusedProgram(CSE_MEMBERS)
        assert eng.kernel_schedule(fused, np.float32, 0) == "rowsum"
        assert eng.kernel_schedule(
            GroupedProgram(fused, num_groups=2), np.float32, 2) == "onehot"
        assert eng.kernel_schedule(HistogramProgram(), np.float32) == ""


# ----------------------------------------------------------------------
# session level: grouped pipeline on the kernel fold path
# ----------------------------------------------------------------------

def make_table(regions=("a", "b", "c", "d"), per=10, seed=0, sites=5):
    r = np.random.default_rng(seed)
    t = make_mip_table(
        payload_shape=PAYLOAD,
        extra_index_columns=[ColumnSpec("age", (), np.float32),
                             ColumnSpec("site", (), np.int32)],
        split_policy=HierarchicalSplitPolicy(max_region_bytes=10**18),
        presplit_keys=list(regions)[1:],
    )
    keys = [f"{g}{i:04d}" for g in regions for i in range(per)]
    n = len(keys)
    t.upload(keys, {
        "img": {"data": r.normal(size=(n,) + PAYLOAD).astype(np.float32)},
        "idx": {"size": r.integers(6_000_000, 20_000_001, n),
                "age": r.uniform(4, 80, n).astype(np.float32),
                "site": r.integers(0, sites, n).astype(np.int32)}})
    return t


def pallas_session(t, **kw):
    return GridSession(t, default_eta=4, fold_impl="pallas",
                       fold_interpret=True, **kw)


class TestSessionDifferential:
    def grouped(self, s):
        return (s.scan().select("img:data").group_by("idx:site")
                .map(MeanProgram()).map(VarianceProgram()).reduce())

    def test_grouped_session_pallas_equals_xla(self):
        res = {}
        for impl in ("pallas", "xla"):
            s = GridSession(make_table(), default_eta=4, fold_impl=impl,
                            fold_interpret=(impl == "pallas"))
            r, _ = self.grouped(s).collect()
            assert s.engine.fold_path_counts[impl] > 0
            assert s.engine.fold_path_counts[
                "xla" if impl == "pallas" else "pallas"] == 0
            res[impl] = r
        assert list(res["pallas"].keys) == list(res["xla"].keys)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float64), np.asarray(b, np.float64),
                rtol=1e-4, atol=1e-3),
            list(res["pallas"].values), list(res["xla"].values))

    def test_grouped_session_matches_numpy_groupby(self):
        t = make_table(seed=3)
        s = pallas_session(t)
        res, rep = self.grouped(s).collect()
        data, sites = t.column("img", "data"), t.column("idx", "site")
        mean, var = res.values
        for g, k in enumerate(res.keys):
            want = data[sites == k]
            np.testing.assert_allclose(np.asarray(mean)[g], want.mean(0),
                                       rtol=1e-4, atol=1e-3)
            np.testing.assert_allclose(np.asarray(var["var"])[g],
                                       want.var(0), rtol=1e-3, atol=1e-3)
        rep.query.check_block_invariant()
        rep.query.check_partial_invariant()

    def test_pallas_and_xla_partials_cache_separately(self):
        """Flipping fold_impl mid-session must re-fold, not merge fp32
        pools accumulated in different orders — the partial key carries
        the implementation."""
        t = make_table()
        s = pallas_session(t)
        s.run(MeanProgram())                 # full pallas partials for a..d
        assert s.engine.fold_path_counts["pallas"] == len(t.regions)
        # regions a+b are fully covered by [a, c): the range query's
        # partial keys match the full-table ones EXCEPT the impl — after
        # the flip nothing may be served from the pallas pool
        s.engine.fold_impl = "xla"
        _, rep = (s.scan(start="a", stop="c")
                  .map(MeanProgram()).collect())
        assert rep.query.partials_reused == 0
        assert rep.query.rows_folded == 20
        assert s.engine.fold_path_counts["xla"] == 2
        # flip back: a fresh range finds the original pallas partials
        s.engine.fold_impl = "pallas"
        _, rep2 = (s.scan(start="a", stop="b")
                   .map(MeanProgram()).collect())
        assert rep2.query.partials_reused == 1
        assert rep2.query.rows_folded == 0

    def test_repeat_grouped_stats_folds_zero_rows(self):
        s = pallas_session(make_table())
        self.grouped(s).stats()
        _, rep = self.grouped(s).collect()
        assert rep.query.rows_folded == 0
        assert rep.query.partials_reused == rep.query.partials_total


class TestGidCache:
    def grouped(self, s):
        return (s.scan().select("img:data").group_by("idx:site")
                .map(MeanProgram()).reduce())

    def test_dirty_region_refold_skips_redensify(self):
        """Satellite acceptance: after a single-region mutation that keeps
        the group universe stable, the re-fold densifies gids ONLY for the
        dirty region — every clean region's gid block is either untouched
        (partial reused) or served from the cache."""
        t = make_table()
        s = pallas_session(t)
        self.grouped(s).stats()
        st0 = s.blocks.stats
        assert st0.gid_builds == len(t.regions)
        key = b"b0003"
        cols = {c: s.retrieve("idx", c, rowkey=key)[1]
                for c in ("age", "site", "size")}
        b0 = st0.gid_builds
        s.upload([key], {
            "img": {"data": np.zeros((1,) + PAYLOAD, np.float32)},
            "idx": cols}, on_duplicate="overwrite")
        _, rep = self.grouped(s).collect()
        dirty = t.regions.region_for(key)
        assert rep.query.rows_folded == dirty.num_rows(t.keys)
        assert s.blocks.stats.gid_builds == b0 + 1   # only the dirty region
        assert s.blocks.gid_count == len(t.regions)

    def test_gid_blocks_shared_across_programs(self):
        """A second grouped plan over the same key column re-folds its own
        partials but serves every gid block from the cache."""
        t = make_table()
        s = pallas_session(t)
        self.grouped(s).stats()
        b0, h0 = s.blocks.stats.gid_builds, s.blocks.stats.gid_hits
        (s.scan().select("img:data").group_by("idx:site")
         .map(MomentsProgram()).reduce().stats())
        assert s.blocks.stats.gid_builds == b0
        assert s.blocks.stats.gid_hits == h0 + len(t.regions)

    def test_clear_partials_drops_gid_blocks(self):
        s = pallas_session(make_table())
        self.grouped(s).stats()
        assert s.blocks.gid_count > 0
        s.blocks.clear_partials()
        assert s.blocks.gid_count == 0


# ----------------------------------------------------------------------
# analytic cost: one-HBM-pass contract
# ----------------------------------------------------------------------

class TestCostModel:
    def test_kernel_bytes_near_one_payload_pass(self):
        """The kernel's HBM traffic is the payload once plus O(R) sidecars
        and O(G·F) write-back — for a realistic block it must stay within
        a few percent of the bare payload size."""
        R, F = 4096, 3072
        payload = R * F * 4
        b = kernel_hbm_bytes(R, F, 4, ("count", "s1", "s2", "s3", "s4"),
                             num_groups=7)
        assert payload < b < 1.05 * payload

    def test_vmem_budget_positive_and_monotone(self):
        full = max_groups_for_vmem()
        assert full > 0
        assert max_groups_for_vmem(("count", "s1")) > full

    @pytest.mark.parametrize("names", [("count", "s1"),
                                       ("count", "s1", "s2"), ACC_ORDER])
    def test_ungrouped_write_back_is_unpadded(self, names):
        """The row-sum schedule writes one pool row a power: the
        write-back is ``names × F × 4`` bytes (4 for the count), where a
        grouped fold writes its sublane-padded 8 rows."""
        R, F = 32, 182 * 218 * 182
        sidecars = R * F * 4 + R * (4 + 4)
        row = sum(4 if n == "count" else F * 4 for n in names)
        assert kernel_hbm_bytes(R, F, 4, names, num_groups=1) \
            - sidecars == row
        assert kernel_hbm_bytes(R, F, 4, names, num_groups=2) \
            - sidecars == 8 * row

    def test_ungrouped_flops_are_a_row_sum(self):
        R, F = 32, 1000
        names = ("count", "s1", "s2")
        # one add a row and column per accumulator, the square, the mask
        assert kernel_flops(R, F, names, num_groups=1) == \
            R + 2 * R * F + R * F + R * F + R
        assert kernel_flops(R, F, names, num_groups=2) == \
            2 * 8 * (R + 2 * R * F) + R * F + R * F + R * 8


# ----------------------------------------------------------------------
# kernel folds by schedule: per-execution counters and the bench reader
# ----------------------------------------------------------------------

class TestKernelFoldCounters:
    def ungrouped(self, s):
        return (s.scan().select("img:data")
                .where(lambda c: c["age"] > 30.0, ["age"])
                .map(MeanProgram()).map(VarianceProgram()).reduce())

    def grouped(self, s):
        return (s.scan().select("img:data").group_by("idx:site")
                .map(MeanProgram()).reduce())

    def test_run_reports_count_folds_by_schedule(self):
        s = pallas_session(make_table())
        _, rep = self.ungrouped(s).collect()
        folded = rep.query.partials_total - rep.query.partials_reused
        assert folded > 0
        assert rep.mapreduce.kernel_folds_rowsum == folded
        assert rep.mapreduce.kernel_folds_onehot == 0
        _, rep_g = self.grouped(s).collect()
        folded_g = rep_g.query.partials_total - rep_g.query.partials_reused
        assert rep_g.mapreduce.kernel_folds_onehot == folded_g > 0
        assert rep_g.mapreduce.kernel_folds_rowsum == 0
        # a repeat is served from the result cache: no fold at all
        _, again = self.ungrouped(s).collect()
        assert again.mapreduce.kernel_folds_rowsum == 0
        m = s.metrics
        assert (m.kernel_folds_rowsum, m.kernel_folds_onehot) == (
            folded, folded_g)
        assert (f"on the fused kernel {folded} row-sum, {folded_g} one-hot"
                in s.describe())

    def test_xla_folds_count_on_neither_schedule(self):
        s = GridSession(make_table(), default_eta=4, fold_impl="xla")
        _, rep = self.ungrouped(s).collect()
        assert rep.query.partials_total > 0
        assert rep.mapreduce.kernel_folds_rowsum == 0
        assert rep.mapreduce.kernel_folds_onehot == 0

    def test_bench_reader_share(self):
        """``bench/metrics/fold.rowsum_share.py``: the row-sum share of
        the window's kernel folds, a report shared by coalesced queries
        counted once; nothing to read without the counters."""
        import types

        from bench.harness import load_metric
        from repro.core.mapreduce import MapReduceStats

        read = load_metric("fold.rowsum_share")

        def report(rowsum, onehot):
            return types.SimpleNamespace(mapreduce=MapReduceStats(
                0, 0, 0, 0, 0, 4, kernel_folds_rowsum=rowsum,
                kernel_folds_onehot=onehot))

        shared = report(3, 0)
        ctx = types.SimpleNamespace(reports=[shared, shared, report(2, 5),
                                             None])
        assert read(ctx) == pytest.approx(50.0)
        assert read(types.SimpleNamespace(
            reports=[report(4, 0)])) == 100.0
        assert read(types.SimpleNamespace(reports=[report(0, 0)])) is None
        older = types.SimpleNamespace(mapreduce=types.SimpleNamespace(
            local_rows_read=10))
        assert read(types.SimpleNamespace(reports=[older])) is None
