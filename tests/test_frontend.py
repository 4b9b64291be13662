"""GridFrontend: concurrent serving, cross-query coalescing, batched ticks,
mutation isolation, admission control — plus the thread-safety substrate
(atomic stats, locked LRU iteration).

Thread counts scale with ``FRONTEND_STRESS_THREADS`` (CI sets it high for
the threaded-stress leg; the default keeps local runs quick).
"""

import os
import threading
import time

import numpy as np
import pytest

from repro.core.blockstore import AtomicStats, LRUCache
from repro.core.frontend import (
    FrontendOverloadedError,
    FrontendStats,
    GridFrontend,
    QueryTimeoutError,
)
from repro.core.grid import GridSession
from repro.core.stats import (
    CountProgram,
    MeanProgram,
    VarianceProgram,
)
from test_grid import make_population, row_batch

STRESS = int(os.environ.get("FRONTEND_STRESS_THREADS", "8"))


def make_session(n=64, split_bytes=2000, **kw):
    return GridSession(make_population(n, split_bytes=split_bytes),
                       default_eta=8, **kw)


def fanout(n, fn):
    """Run ``fn(i)`` on n threads released by one barrier; re-raise the
    first worker exception in the caller."""
    barrier = threading.Barrier(n)
    errors = []

    def run(i):
        try:
            barrier.wait()
            fn(i)
        except BaseException as e:   # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


class TestCoalescing:
    def test_barrier_identical_cold_queries_fold_once(self):
        """N concurrent identical queries: one execution, one fold per
        block, N-1 coalesce hits — the headline acceptance criterion."""
        s = make_session()
        plan = s.scan().map(MeanProgram()).reduce()
        n_regions = len(s.table.regions)
        assert n_regions > 1
        expect = s.table.column("img", "data").mean(axis=0)
        N = max(8, STRESS)
        futs = [None] * N
        with GridFrontend(s, workers=4, tick_ms=5.0) as fe:
            fanout(N, lambda i: futs.__setitem__(i, fe.submit(plan)))
            results = [f.result(timeout=120) for f in futs]
            stats = fe.stats.snapshot()
        for val, _rep in results:
            np.testing.assert_allclose(np.asarray(val), expect, atol=1e-5)
        assert stats.coalesce_hits >= N - 1
        assert stats.served == N
        # exactly one fold dispatch per block, however many clients asked
        store = s.blocks.stats.snapshot()
        assert store.folds == n_regions
        assert sum(s.engine.fold_path_counts.values()) == n_regions

    def test_warm_coalesce_zero_folds(self):
        s = make_session()
        plan = s.scan().map(MeanProgram()).reduce()
        with GridFrontend(s, workers=4, tick_ms=2.0) as fe:
            fe.query(plan, timeout=120)           # warm: result cache filled
            folds0 = s.blocks.stats.folds
            N = max(8, STRESS)
            futs = [None] * N
            fanout(N, lambda i: futs.__setitem__(i, fe.submit(plan)))
            for f in futs:
                f.result(timeout=120)
            assert fe.stats.coalesce_hits >= N - 1
        assert s.blocks.stats.folds == folds0

    def test_sequential_submissions_coalesce_until_mutation(self):
        """Completed flights are retained, so repeats coalesce without
        temporal overlap; a mutation clears the registry."""
        s = make_session()
        plan = s.scan().map(CountProgram()).reduce()
        with GridFrontend(s, workers=2, tick_ms=0.0) as fe:
            v1, _ = fe.query(plan, timeout=120)
            v2, _ = fe.query(plan, timeout=120)
            assert int(v1) == int(v2) == 64
            assert fe.stats.coalesce_hits >= 1
            scans_before = s.metrics.scans
            fe.upload(["zz1", "zz2"], row_batch(["zz1", "zz2"]))
            v3, _ = fe.query(plan, timeout=120)
            assert int(v3) == 66
            assert s.metrics.scans > scans_before   # re-executed, not replayed

    def test_no_coalesce_mode_executes_each_query(self):
        s = make_session()
        plan = s.scan().map(MeanProgram()).reduce()
        N = 6
        futs = [None] * N
        with GridFrontend(s, workers=4, tick_ms=2.0, coalesce=False) as fe:
            fanout(N, lambda i: futs.__setitem__(i, fe.submit(plan)))
            for f in futs:
                f.result(timeout=120)
            assert fe.stats.coalesce_hits == 0
            assert fe.stats.batch_merges == 0
            assert fe.stats.served == N
        assert s.metrics.scans == N     # every query its own execution
        # without the fold gate, concurrent misses may duplicate folds
        # (same content, wasted work — the control arm the bench measures)
        assert s.blocks.stats.folds >= len(s.table.regions)

    def test_fold_gate_single_flight(self):
        """The partial-level gate: concurrent misses on one pkey run the
        fold once; followers get the leader's result as coalesced."""
        s = make_session()
        with GridFrontend(s, workers=2) as fe:
            calls = []
            lock = threading.Lock()

            def slow_fold():
                with lock:
                    calls.append(1)
                time.sleep(0.2)
                return ("partial", None, False, False)

            N = max(8, STRESS)
            out = [None] * N
            fanout(N, lambda i: out.__setitem__(
                i, s.fold_gate(("pkey",), slow_fold)))
            assert len(calls) == 1
            assert all(res == ("partial", None, False, False)
                       for res, _ in out)
            assert sum(1 for _, coalesced in out if coalesced) == N - 1
            assert fe.stats.partial_coalesce_hits == N - 1


class TestBatchedTicks:
    def test_distinct_programs_merge_into_one_pass(self):
        s = make_session()
        t = s.table
        p1 = s.scan().map(VarianceProgram()).reduce()
        p2 = s.scan().map(CountProgram()).reduce()
        out = [None, None]
        with GridFrontend(s, workers=4, tick_ms=20.0) as fe:
            fanout(2, lambda i: out.__setitem__(
                i, fe.submit(p1 if i == 0 else p2)))
            (v1, rep1), (v2, rep2) = (out[0].result(120),
                                      out[1].result(120))
            assert fe.stats.batch_merges == 1
            assert fe.stats.batched_queries == 2
        np.testing.assert_allclose(
            np.asarray(v1["var"]), t.column("img", "data").var(axis=0),
            atol=1e-4)
        assert int(v2) == 64
        # both plans share one scan resolution and one fold pass
        assert rep1 is rep2
        assert s.metrics.scans == 1

    def test_grouped_plans_merge_and_split(self):
        s = make_session()
        t = s.table
        g1 = s.scan().group_by("idx:sex").map(MeanProgram()).reduce()
        g2 = s.scan().group_by("idx:sex").map(CountProgram()).reduce()
        out = [None, None]
        with GridFrontend(s, workers=4, tick_ms=20.0) as fe:
            fanout(2, lambda i: out.__setitem__(
                i, fe.submit(g1 if i == 0 else g2)))
            gr1, _ = out[0].result(120)
            gr2, _ = out[1].result(120)
            assert fe.stats.batch_merges == 1
        sex = t.column("idx", "sex")
        data = t.column("img", "data")
        np.testing.assert_array_equal(gr1.keys, np.unique(sex))
        for gi, k in enumerate(gr1.keys):
            np.testing.assert_allclose(
                np.asarray(gr1.values)[gi], data[sex == k].mean(axis=0),
                atol=1e-4)
            assert int(np.asarray(gr2.values)[gi]) == int((sex == k).sum())

    def test_multi_column_plans_merge_and_split(self):
        s = make_session()
        t = s.table
        cols = ["img:data", "idx:age"]
        m1 = s.scan().select(cols).map(MeanProgram()).reduce()
        m2 = s.scan().select(cols).map(CountProgram()).reduce()
        out = [None, None]
        with GridFrontend(s, workers=4, tick_ms=20.0) as fe:
            fanout(2, lambda i: out.__setitem__(
                i, fe.submit(m1 if i == 0 else m2)))
            mv1, _ = out[0].result(120)
            mv2, _ = out[1].result(120)
        assert set(mv1) == set(cols)
        np.testing.assert_allclose(
            np.asarray(mv1["idx:age"]), t.column("idx", "age").mean(),
            atol=1e-3)
        assert int(mv2["img:data"]) == 64

    def test_different_scans_do_not_merge(self):
        s = make_session()
        pa = s.scan(prefix=b"img0000").map(CountProgram()).reduce()
        pb = s.scan().map(CountProgram()).reduce()
        out = [None, None]
        with GridFrontend(s, workers=4, tick_ms=20.0) as fe:
            fanout(2, lambda i: out.__setitem__(
                i, fe.submit(pa if i == 0 else pb)))
            va, _ = out[0].result(120)
            vb, _ = out[1].result(120)
            assert fe.stats.batch_merges == 0
        assert int(va) == 10 and int(vb) == 64


class TestMutationIsolation:
    def test_queries_never_observe_partial_uploads(self):
        """Counts observed under interleaved 2-row uploads are always in
        the set of committed totals — the epoch write lock admits no
        torn reads."""
        s = make_session()
        rounds, batch = 4, 2
        valid = {64 + r * batch for r in range(rounds + 1)}
        observed = []
        obs_lock = threading.Lock()
        stop = threading.Event()

        with GridFrontend(s, workers=4, tick_ms=0.0) as fe:
            def reader(i):
                while not stop.is_set():
                    plan = s.scan().map(CountProgram()).reduce()
                    val, _ = fe.query(plan, timeout=120)
                    with obs_lock:
                        observed.append(int(val))

            threads = [threading.Thread(target=reader, args=(i,))
                       for i in range(max(4, STRESS // 2))]
            for t in threads:
                t.start()
            try:
                for r in range(rounds):
                    keys = [f"zz{r}_{j}" for j in range(batch)]
                    fe.upload(keys, row_batch(keys, seed=r + 10))
                    time.sleep(0.05)
            finally:
                stop.set()
                for t in threads:
                    t.join()
            assert fe.stats.mutations == rounds
        assert observed, "readers made no progress"
        assert set(observed) <= valid, (
            f"torn reads: {sorted(set(observed) - valid)}")
        final, _ = s.scan().map(CountProgram()).reduce().collect()
        assert int(final) == 64 + rounds * batch

    def test_mutation_drains_in_flight_query(self):
        """An upload issued while a slow query executes waits for it; the
        slow query's answer reflects the pre-mutation epoch."""
        s = make_session()
        entered = threading.Event()

        def slow_pred(cols):
            entered.set()
            time.sleep(0.4)
            return cols["age"] > -np.inf          # selects everything

        plan = s.scan().where(slow_pred, ["age"]).map(
            CountProgram()).reduce()
        with GridFrontend(s, workers=2, tick_ms=0.0) as fe:
            fut = fe.submit(plan)
            assert entered.wait(timeout=30)
            t0 = time.monotonic()
            fe.upload(["zz1"], row_batch(["zz1"]))
            drained = time.monotonic() - t0
            val, _ = fut.result(timeout=120)
        assert int(val) == 64            # pre-upload snapshot
        assert drained > 0.05            # the writer actually waited


class TestAdmission:
    def _slow_plan(self, s, delay=0.5, seed=0):
        def slow_pred(cols, _d=delay):
            time.sleep(_d)
            return cols["age"] > -np.inf

        return s.scan().where(slow_pred, ["age"]).map(
            CountProgram()).reduce()

    def test_backpressure_rejects_beyond_max_pending(self):
        s = make_session()
        with GridFrontend(s, workers=1, tick_ms=0.0,
                          max_pending=2) as fe:
            first = fe.submit(self._slow_plan(s))
            with pytest.raises(FrontendOverloadedError):
                for _ in range(4):
                    fe.submit(self._slow_plan(s))
            assert fe.stats.rejected >= 1
            first.result(timeout=120)

    def test_deadline_expires_queued_query(self):
        s = make_session()
        with GridFrontend(s, workers=1, tick_ms=0.0) as fe:
            blocker = fe.submit(self._slow_plan(s))
            doomed = fe.submit(s.scan().map(CountProgram()).reduce(),
                               deadline=0.01)
            with pytest.raises(QueryTimeoutError):
                doomed.result(timeout=120)
            assert fe.stats.timeouts == 1
            blocker.result(timeout=120)
            # the frontend still serves after a timeout
            val, _ = fe.query(s.scan().map(CountProgram()).reduce(),
                              timeout=120)
            assert int(val) == 64

    def test_deadline_enforced_during_execution(self):
        """A query whose deadline passes AFTER dispatch aborts at the
        next fold-gate entry instead of running to completion."""
        s = make_session()
        with GridFrontend(s, workers=1, tick_ms=0.0) as fe:
            doomed = fe.submit(self._slow_plan(s, delay=0.6),
                               deadline=0.15)
            with pytest.raises(QueryTimeoutError):
                doomed.result(timeout=120)
            assert fe.stats.timeouts == 1
            assert fe.stats.served == 0
            # aborted before folding a single block
            assert s.blocks.stats.folds == 0
            # the flight was released: the identical plan re-executes
            val, _ = fe.query(self._slow_plan(s, delay=0.0), timeout=120)
            assert int(val) == 64
            assert fe.stats.served == 1 and fe.stats.timeouts == 1

    def test_timed_out_sync_query_is_abandoned_once(self):
        """query(timeout=) that gives up must settle its task exactly
        once (as a timeout) and release the flight — the old behaviour
        left the task running and counted it ``served``."""
        s = make_session()
        with GridFrontend(s, workers=1, tick_ms=0.0) as fe:
            blocker = fe.submit(self._slow_plan(s))
            plan = s.scan().map(CountProgram()).reduce()
            with pytest.raises(QueryTimeoutError):
                fe.query(plan, timeout=0.05)
            assert fe.stats.timeouts == 1
            blocker.result(timeout=120)
            # resubmitting is NOT coalesced onto the abandoned flight
            val, _ = fe.query(s.scan().map(CountProgram()).reduce(),
                              timeout=120)
            assert int(val) == 64
            snap = fe.stats.snapshot()
            assert snap.served == 2          # blocker + the retry
            assert snap.failed == 1          # the abandoned task, once
            assert snap.timeouts == 1
            assert snap.served + snap.failed == snap.submitted

    def test_submit_after_close_raises(self):
        s = make_session()
        fe = GridFrontend(s, workers=1)
        fe.close()
        with pytest.raises(RuntimeError):
            fe.submit(s.scan().map(CountProgram()).reduce())
        assert s.fold_gate is None       # hook released

    def test_double_close_is_idempotent(self):
        s = make_session()
        fe = GridFrontend(s, workers=1)
        fe.query(s.scan().map(CountProgram()).reduce(), timeout=120)
        fe.close()
        fe.close()                       # second close: clean no-op
        assert s.fold_gate is None
        # and the context manager may wrap an already-closed frontend
        with fe:
            pass

    def test_close_drains_in_flight_work(self):
        """close() called while queries are executing and a mutation is
        queued behind them: everything submitted before the close
        resolves (no dangling futures), then the frontend shuts down."""
        s = make_session()
        fe = GridFrontend(s, workers=2, tick_ms=0.0)
        futs = [fe.submit(s.scan().map(CountProgram()).reduce())
                for _ in range(4)]
        done = threading.Event()

        def mutate():
            fe.upload(["zzclose"], row_batch(["zzclose"]))
            done.set()

        mut = threading.Thread(target=mutate)
        mut.start()
        fe.close()
        mut.join(timeout=120)
        assert done.is_set(), "mutation queued before close must complete"
        for f in futs:
            val, _rep = f.result(timeout=120)   # resolved, not abandoned
            assert int(val) in (64, 65)
        assert s.table.num_rows == 65
        snap = fe.stats.snapshot()
        assert snap.served == snap.submitted == 4
        assert snap.mutations == 1


class TestThreadSafetySubstrate:
    def test_lru_iteration_safe_under_concurrent_eviction(self):
        """keys()/values()/items() snapshots never raise while another
        thread churns the cache past its cap."""
        cache = LRUCache(32)
        stop = threading.Event()
        errors = []

        def churn():
            i = 0
            while not stop.is_set():
                cache.put(i % 100, i)
                cache.get((i * 7) % 100)
                i += 1

        def walk():
            try:
                while not stop.is_set():
                    for k, v in cache.items():
                        assert v is not None
                    list(cache.keys())
                    list(cache.values())
            except RuntimeError as e:    # "dict changed size" = the bug
                errors.append(e)

        threads = ([threading.Thread(target=churn) for _ in range(3)]
                   + [threading.Thread(target=walk) for _ in range(3)])
        for t in threads:
            t.start()
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join()
        assert not errors

    def test_atomic_stats_exact_under_contention(self):
        stats = FrontendStats()
        N, per = max(8, STRESS), 500
        fanout(N, lambda i: [stats.inc(served=1, submitted=2)
                             for _ in range(per)])
        assert stats.served == N * per
        assert stats.submitted == 2 * N * per

    def test_atomic_stats_imax_monotone(self):
        stats = FrontendStats()
        fanout(8, lambda i: [stats.imax(queue_depth_peak=d)
                             for d in range(100)])
        assert stats.queue_depth_peak == 99

    def test_snapshot_is_consistent(self):
        """inc() batches two counters atomically; snapshot() never sees
        them apart."""
        stats = FrontendStats()
        stop = threading.Event()
        torn = []

        def bump():
            while not stop.is_set():
                stats.inc(served=1, submitted=1)

        def observe():
            while not stop.is_set():
                snap = stats.snapshot()
                if snap.served != snap.submitted:
                    torn.append((snap.served, snap.submitted))

        threads = ([threading.Thread(target=bump) for _ in range(4)]
                   + [threading.Thread(target=observe) for _ in range(2)])
        for t in threads:
            t.start()
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join()
        assert not torn

    def test_blockstore_stats_snapshot(self):
        s = make_session()
        s.scan().map(CountProgram()).reduce().collect()
        snap = s.blocks.stats.snapshot()
        assert snap.folds == len(s.table.regions)
        # detached copy: live counters keep moving, the snapshot doesn't
        s.upload(["zz1"], row_batch(["zz1"]))
        s.scan().map(CountProgram()).reduce().collect()
        assert s.blocks.stats.folds > snap.folds


class TestFrontendStats:
    def test_queue_depth_peak_observed(self):
        s = make_session()
        plan_a = s.scan().map(MeanProgram()).reduce()
        plan_b = s.scan(prefix=b"img0000").map(MeanProgram()).reduce()
        with GridFrontend(s, workers=1, tick_ms=50.0) as fe:
            fa, fb = fe.submit(plan_a), fe.submit(plan_b)
            fa.result(timeout=120)
            fb.result(timeout=120)
            assert fe.stats.queue_depth_peak >= 2
