"""Program spans and per-query timing records (``repro.core.spans``).

A query served through ``GridFrontend`` carries a ``QueryTrace`` on its
``RunReport``: queue wait, time per span (total and self), backend
compiles and device wait.  The same spans land in a profiler trace as
TraceMe events on the profiler's clock, and the benchmark's per-layer
metrics read the records.
"""

import glob
import math
import os
import tempfile
import time
import types

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import harness
from repro.core import spans
from repro.core.frontend import GridFrontend
from repro.core.grid import GridSession, RunReport
from repro.core.query import age_sex_predicate
from repro.core.stats import MeanProgram, VarianceProgram
from test_grid import make_population

#: the spans whose self times, with the queue and device waits, split a
#: query's latency without overlap
PHASES = ("grid.plan", "blockstore.fetch", "fold.dispatch", "merge.dispatch")


def make_session(n=48, payload=(3, 4), split_bytes=2000):
    return GridSession(make_population(n, payload=payload,
                                       split_bytes=split_bytes),
                       default_eta=8)


def subset_plan(s, lo, hi):
    return (s.scan().select(("img", "data"))
            .where(age_sex_predicate(lo, hi, None), ["age", "sex"])
            .map(MeanProgram()).map(VarianceProgram()).reduce())


def wait_device(trace, timeout=60.0):
    """Poll until the frontend's watcher stamped the device wait."""
    t_end = time.monotonic() + timeout
    while trace.device_s is None:
        assert time.monotonic() < t_end, "device wait never stamped"
        time.sleep(0.005)


class TestQueryTrace:
    def test_frontend_query_records_its_phases(self):
        s = make_session()
        plan = s.scan().map(MeanProgram()).reduce()
        with GridFrontend(s, workers=2, tick_ms=1.0) as fe:
            t0 = time.time_ns()
            res, rep = fe.submit(plan).result(timeout=120)
            jax.block_until_ready(res)
            tr = rep.trace
            wait_device(tr)
            wall_s = (time.time_ns() - t0) / 1e9
        q = rep.query
        folded = q.partials_total - q.partials_reused
        assert folded == len(s.table.regions) > 1
        assert tr.queue_s >= 0.0
        assert tr.spans["fold.dispatch"].count == folded
        assert tr.spans["merge.dispatch"].count == 1
        assert tr.spans["grid.execute"].count == 1
        assert tr.device_s >= 0.0
        phases = (tr.queue_s + sum(tr.self_s(n) for n in PHASES)
                  + tr.device_s)
        assert phases <= wall_s
        # self time never exceeds the span's own duration
        for st in tr.spans.values():
            assert 0.0 <= st.self_s <= st.total_s

    def test_each_query_gets_its_own_qid(self):
        s = make_session()
        with GridFrontend(s, workers=2, tick_ms=1.0, coalesce=False) as fe:
            _, a = fe.submit(subset_plan(s, 4, 40)).result(timeout=120)
            _, b = fe.submit(subset_plan(s, 30, 70)).result(timeout=120)
        assert a.trace.qid != b.trace.qid
        assert a.trace is not b.trace

    def test_direct_session_call_gets_a_record(self):
        s = make_session()
        _, rep = s.scan().map(MeanProgram()).reduce().collect()
        tr = rep.trace
        assert tr.spans["grid.execute"].count == 1
        assert tr.spans["fold.dispatch"].count == len(s.table.regions)
        assert tr.queue_s is None and tr.device_s is None
        totals = spans.totals()
        assert totals["grid.execute"].count >= 1
        assert "span fold.dispatch:" in s.describe()


@pytest.fixture(scope="module")
def traced_query():
    """One query through the frontend under the profiler, and the trace."""
    s = make_session(payload=(2, 3))
    plan = subset_plan(s, 10, 60)
    log_dir = tempfile.mkdtemp(prefix="spans-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with GridFrontend(s, workers=2, tick_ms=1.0, coalesce=False) as fe:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            res, rep = fe.submit(plan).result(timeout=120)
            jax.block_until_ready(res)
        finally:
            jax.profiler.stop_trace()
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)
    assert path, "the profiler wrote no trace"
    return rep.trace, ProfileData.from_file(path[0])


def host_spans(pd, qid):
    """``{name: [(start_ns, end_ns, line)]}`` of the query's span events."""
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if dict(ev.stats).get("qid") == qid:
                    out.setdefault(ev.name, []).append(
                        (int(ev.start_ns), int(ev.end_ns),
                         (plane.name, line.name)))
    return out


class TestProfilerTrace:
    def test_spans_nest_in_execute_with_the_qid(self, traced_query):
        tr, pd = traced_query
        found = host_spans(pd, tr.qid)
        for name in ("frontend.submit", "grid.execute", "grid.plan",
                     "fold.dispatch", "merge.dispatch"):
            assert name in found, (name, sorted(found))
        (lo, hi, line), = found["grid.execute"]
        for name in ("grid.plan", "fold.dispatch", "merge.dispatch"):
            for s, e, ln in found[name]:
                assert ln == line and lo <= s <= e <= hi, name
        assert len(found["fold.dispatch"]) == tr.spans["fold.dispatch"].count

    def test_record_and_trace_share_a_clock(self, traced_query):
        tr, pd = traced_query
        env = {p.name: dict(p.stats) for p in pd.planes}
        profile_start = int(env["Task Environment"]["profile_start_time"])
        (start, _, _), = host_spans(pd, tr.qid)["grid.execute"]
        gap_ns = abs(profile_start + start - tr.spans["grid.execute"].start_ns)
        assert gap_ns < 5e6, gap_ns


class TestCompiles:
    def test_new_shape_compiles_and_repeat_does_not(self):
        # a payload width and row count no other test here uses
        s = make_session(n=37, payload=(5, 7), split_bytes=6 * 10**7)
        with GridFrontend(s, workers=1, tick_ms=0.0, coalesce=False) as fe:
            _, first = fe.submit(subset_plan(s, 4, 50)).result(timeout=120)
            _, again = fe.submit(subset_plan(s, 20, 70)).result(timeout=120)
        assert again.query.partials_reused == 0        # a fresh mask
        assert again.query.partials_total == first.query.partials_total > 1
        assert first.trace.compiles >= 1
        assert first.trace.compile_s > 0.0
        assert sum(st.compiles for st in first.trace.spans.values()) >= 1
        assert again.trace.compiles == 0


# ----------------------------------------------------------------------
# the benchmark's per-layer metrics over hand-built records
# ----------------------------------------------------------------------

def record(queue_ms, device_ms, plan_ms, fetch_ms, dispatch_ms, compiles):
    tr = spans.QueryTrace(queue_s=queue_ms / 1e3, device_s=device_ms / 1e3,
                          compiles=compiles)
    for name, ms in (("grid.plan", plan_ms), ("blockstore.fetch", fetch_ms),
                     ("fold.dispatch", dispatch_ms)):
        tr.spans[name] = spans.SpanStat(count=1, total_s=2 * ms / 1e3,
                                        self_s=ms / 1e3)
    return RunReport(epoch=0, eta=8, plan_cache_hit=False, mapreduce=None,
                     trace=tr)


def records_ctx():
    reps = [record(q, 10 * q, 0.1 * q, 0.2 * q, 0.5 * q, int(q % 3 == 0))
            for q in range(1, 21)]
    # a report shared by coalesced queries counts once; a failed query
    # has none
    return types.SimpleNamespace(reports=reps + [reps[0], reps[0], None])


@pytest.mark.parametrize("name, expect", [
    ("frontend.queue_wait_p95_ms", 19.0),
    ("device.query_wait_p95_ms", 190.0),
    ("planner.host_ms_per_query", 0.1 * 10.5),
    ("blockstore.fetch_ms_per_query", 0.2 * 10.5),
    ("fold.dispatch_ms_per_query", 0.5 * 10.5),
    ("device.compiles_in_window", 6.0),
])
def test_span_metrics_read_the_records(name, expect):
    read = harness.load_metric(name)
    assert math.isclose(read(records_ctx()), expect, rel_tol=1e-9)
    bare = RunReport(epoch=0, eta=8, plan_cache_hit=False, mapreduce=None)
    assert read(types.SimpleNamespace(reports=[None, bare])) is None
    # reports of a program without timing records
    old = types.SimpleNamespace(query=None, mapreduce=None)
    assert read(types.SimpleNamespace(reports=[old])) is None
