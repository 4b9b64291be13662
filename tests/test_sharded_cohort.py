"""The four-chip sharded cohort on four forced CPU devices: the benchmark
cell ``t1_mni152_1mm_x4.subset_open`` through its harness at a tiny
volume, the tree merge against the funnel and a float64 reference under
concurrent callers, and each query's own merge path.

Each test runs in a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, since this
process keeps one device; the child's timeout turns a hang (two
collectives waiting on each other) into a failure.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(body: str, timeout: float = 300) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)], cwd=REPO,
        capture_output=True, text=True, env=env, timeout=timeout)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n" \
        f"{proc.stderr[-20000:]}"
    return proc.stdout


def test_x4_cell_merges_every_answer_by_the_tree():
    out = run_child(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        from bench import harness

        assert jax.device_count() == 4
        # no device plane on the CPU: the reducer reads the committed trace
        recorded = harness.tracefile.reduce_trace(
            harness.os.path.join(harness.BENCH, 'testdata', 'trace'))
        harness.tracefile.reduce_trace = lambda path, inflight=(): recorded
        seen = {{}}

        def capture(run):
            window = run.window

            def keep(*args, **kwargs):
                seen.update(window(*args, **kwargs))
                seen['widths'] = {{k[2] for k in run.session.engine._compiled
                                   .keys() if k[0] == 'bpresum'}}
                return seen

            run.window = keep
            seen['slots'] = run.session._merge_slots()

        cell = harness.Cell.find('t1_mni152_1mm_x4.subset_open')
        res = harness.execute(cell, 2**31 + 7, 2.0, True, t_start=0.0,
                              devices=jax.devices()[:4], shape=(4, 5, 6),
                              interpret=True, tamper=capture)
        assert res['correct'] and res['failed'] == 0, res['checks']
        reports = [r for r in seen['reports'] if r is not None]
        assert len(reports) == res['attempted'] > 0
        assert {{r.query.merge_path for r in reports}} == {{'tree'}}
        assert all(r.query.cross_chip_bytes == 0 for r in reports)
        assert all(len(r.query.folds_per_owner) == 4 for r in reports)
        assert sum(sum(r.query.folds_per_owner) for r in reports) == \\
            sum(r.query.partials_total - r.query.partials_reused
                for r in reports)
        # every owner-local sum is 8 wide, however the blocks fell
        assert seen['slots'] == 8 and seen['widths'] == {{8}}
        assert seen['compiles'] == 0
        assert sum(r.trace.compiles for r in reports) == 0
        m = {{k: v['value'] for k, v in res['metrics'].items()}}
        assert m['device.compiles_in_window'] == 0
        assert m['fold.compiles_in_window'] == 0
        assert m['mesh.cross_chip_mb_per_query'] == 0
        assert m['merge.tree_host_ms_per_query'] > 0
        assert m['mesh.owner_fold_skew'] >= 0
        print('X4_CELL_OK', len(reports), m['mesh.owner_fold_skew'])
    """, timeout=600)
    assert "X4_CELL_OK" in out


def test_tree_merge_matches_funnel_and_float64_from_four_threads():
    out = run_child("""
        import threading
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.mapreduce import MapReduceEngine, MergeRecord
        from repro.core.stats import FusedProgram, MeanProgram, VarianceProgram

        devs = jax.devices()[:4]
        mesh = Mesh(np.array(devs), ('data',))
        tree = MapReduceEngine(mesh, fold_interpret=True)
        funnel = MapReduceEngine(mesh, merge_strategy='funnel',
                                 fold_interpret=True)
        prog = FusedProgram((MeanProgram(), VarianceProgram()))
        shape, rows = (3, 4, 5), 8
        rng = np.random.default_rng(0)
        # uneven owners: 0, 1, 3 and 8 blocks
        owners = [1] + [2] * 3 + [3] * 8
        blocks = [rng.standard_normal((rows, 60)).astype(np.float32) + k
                  for k in range(len(owners))]
        masks = [rng.random(rows) < 0.6 for _ in owners]
        partials = [tree.fold_block(prog, jax.device_put(b, devs[o]), m, 4,
                                    shape, np.float32, owner=o)
                    for b, m, o in zip(blocks, masks, owners)]
        sel = np.concatenate([b[m] for b, m in zip(blocks, masks)])
        sel = sel.astype(np.float64).reshape(-1, *shape)
        want_mean, want_var = sel.mean(0), sel.var(0)

        ref_mean, ref_var = funnel.merge_finalize(
            prog, partials, shape, np.float32, owners=owners)
        results, errors = {}, []

        def merge(i):
            try:
                for k in range(5):
                    rec = MergeRecord()
                    got = tree.merge_finalize(prog, partials, shape,
                                              np.float32, owners=owners,
                                              slots=8, record=rec)
                    results[i, k] = (jax.device_get(got), rec)
            except Exception as e:
                errors.append(repr(e))

        threads = [threading.Thread(target=merge, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
            assert not th.is_alive(), 'a tree merge hung'
        assert not errors, errors
        assert len(results) == 20
        for (mean, var), rec in results.values():
            assert rec.path == 'tree' and rec.cross_chip_bytes == 0
            np.testing.assert_allclose(mean, ref_mean, rtol=2e-6)
            np.testing.assert_allclose(var['var'], ref_var['var'], rtol=1e-5)
            np.testing.assert_allclose(mean, want_mean, rtol=2e-6)
            np.testing.assert_allclose(var['var'], want_var, rtol=1e-5)
            assert var['count'] == len(sel)
        # one presum width for every owner and every query, however many
        # blocks each owner holds: a query on 4 blocks sums 8 wide too
        few = tree.merge_finalize(prog, partials[:4], shape, np.float32,
                                  owners=owners[:4], slots=8)
        few_sel = np.concatenate([b[m] for b, m in zip(blocks[:4],
                                                       masks[:4])])
        np.testing.assert_allclose(jax.device_get(few[0]),
                                   few_sel.mean(0).reshape(shape), rtol=2e-6)
        assert [k[2] for k in tree._compiled.keys()
                if k[0] == 'bpresum'] == [8]

        # a partial away from its owner is re-homed, and counted
        moved = [jax.device_put(partials[0], devs[0])] + partials[1:]
        rec = MergeRecord()
        mean, _ = tree.merge_finalize(prog, moved, shape, np.float32,
                                      owners=owners, slots=8, record=rec)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(partials[0]))
        assert rec.path == 'tree' and rec.cross_chip_bytes == nbytes
        np.testing.assert_allclose(jax.device_get(mean), ref_mean, rtol=2e-6)
        print('TREE_THREADS_OK')
    """)
    assert "TREE_THREADS_OK" in out


def test_concurrent_queries_each_report_their_own_merge_path():
    out = run_child("""
        import threading
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.grid import GridSession
        from repro.core.stats import MeanProgram
        from repro.core.table import make_mip_table

        groups = [f'g{i:02d}' for i in range(8)]
        t = make_mip_table(payload_shape=(2, 3), presplit_keys=groups[1:])
        keys = [f'{g}x{i:03d}' for g in groups for i in range(5)]
        rng = np.random.default_rng(0)
        t.upload(keys, {'img': {'data': rng.normal(
                            size=(len(keys), 2, 3)).astype(np.float32)},
                        'idx': {'size': rng.integers(
                            6_000_000, 20_000_001, len(keys))}})

        def plans(s):
            # every region (several partials) and one region (one partial)
            return (s.scan().select('img:data').map(MeanProgram()).reduce(),
                    s.scan(prefix='g03').select('img:data')
                    .map(MeanProgram()).reduce())

        def race(devices, want):
            # no result cache: every execution merges
            s = GridSession(t, mesh=Mesh(np.array(devices), ('data',)),
                            default_eta=4, plan_cache_cap=0)
            wide, narrow = plans(s)
            got, errors = [], []
            barrier = threading.Barrier(2)

            def ask(i, plan):
                try:
                    for k in range(6):
                        barrier.wait(timeout=60)
                        _, rep = plan.collect()
                        got.append((i, rep.query.merge_path))
                except Exception as e:
                    errors.append(repr(e))
                    barrier.abort()

            threads = [threading.Thread(target=ask, args=(i, p))
                       for i, p in enumerate((wide, narrow))]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
                assert not th.is_alive(), 'a query hung'
            assert not errors, errors
            assert sorted(set(got)) == sorted(want.items()), got

        race(jax.devices()[:1], {0: 'funnel', 1: 'funnel'})
        race(jax.devices()[:4], {0: 'tree', 1: 'funnel'})
        print('OWN_PATH_OK')
    """)
    assert "OWN_PATH_OK" in out
