"""One run of one cell: set-up, the measured window, the check.

``Cell`` finds everything by name: the cell in ``BENCHMARK.json``, its
configuration file, ``mixes/<traffic>.json``, ``cells/<cell>.json`` (the
fixed rate and the correctness limits) and ``metrics/<metric>.py`` for
each per-layer metric.

Set-up draws the cohort, uploads it, opens a ``GridSession`` (fused
Pallas fold, a data mesh of exactly the cell's devices) behind a
``GridFrontend``, commits every block through one warm query and warms
the fold and merge shapes the window's schedule will use.  The window
offers the schedule open loop: a generator thread submits each query at
its due time, each answer's arrays are copied to the host by one of two
fetch threads, and a query's latency runs from its due time to that
copy.  The check compares a sample of the answers with the float64
reference once the window has closed and the system's state is freed.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import queue
import resource
import shutil
import sys
import tempfile
import threading
import time
import types
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cohort as cohort_mod  # noqa: E402
from bench import reference, traffic, tracefile, work  # noqa: E402
from bench.peaks import peak_for  # noqa: E402

#: how long past the window's close the harness waits for late answers
DRAIN_S = 60.0
FETCH_THREADS = 2


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def host_memory(point: str, trace_dir: Optional[str] = None) -> None:
    """Log the process's peak host RSS (``ru_maxrss``, KiB on Linux) and
    its RSS now at one point of a run, with the trace file's size where
    there is one.  Not a metric: it shows where a cell that outgrows the
    host spends its memory."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    msg = f"host memory {point}: peak RSS {peak / 2**30:.3f} GiB"
    try:
        with open("/proc/self/statm") as f:
            now = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
        msg += f", RSS now {now / 2**30:.3f} GiB"
    except OSError:
        pass
    if trace_dir is not None:
        size = os.path.getsize(tracefile.find_xplane(trace_dir))
        msg += f", trace file {size / 1e6:.1f} MB"
    log(msg)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with the files it names."""

    spec: Dict
    workload: Dict
    config: Dict
    mix: Dict
    rate: float
    limits: Dict[str, float]

    @classmethod
    def find(cls, name: str, root: str = ROOT) -> "Cell":
        spec = load_json(os.path.join(root, "BENCHMARK.json"))
        wl = {w["name"]: w for w in spec["workloads"]}.get(name)
        if wl is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
        config = load_json(os.path.join(root, cfg_entry["file"]))
        mix = load_json(os.path.join(BENCH, "mixes", wl["traffic"] + ".json"))
        cell = load_json(os.path.join(BENCH, "cells", name + ".json"))
        return cls(spec, wl, config, mix, float(cell["rate_per_s"]),
                   {k: float(cell["limits"][k]) for k in reference.NUMBERS})

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metrics(self, kind: str) -> List[Dict]:
        """This cell's ``end_to_end`` or ``per_layer`` metrics."""
        return [m for m in self.spec[kind]
                if self.name in m.get("workloads", [self.name])]


def load_metric(name: str) -> Callable[[Any], Optional[float]]:
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def nearest_rank(values: Sequence[float], p: float) -> float:
    """The ``p``-quantile by nearest rank (``inf`` counts as late)."""
    s = sorted(values)
    if not s:
        return float("nan")
    return s[max(0, math.ceil(p * len(s)) - 1)]


def use_compile_cache(jax) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout;
    every executable is written, so later runs compile nothing."""
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


def describe_layout(session) -> Dict[str, Any]:
    table = session.table
    counts = table.region_row_counts()
    rows = [counts[r.rid] for r in table.regions]
    spec = table.column_spec("img", "data")
    buckets = [session.engine.bucket_rows(r) for r in rows]
    return {"subjects": table.num_rows,
            "volume": "x".join(map(str, spec.shape)),
            "payload_bytes": table.num_rows * spec.row_nbytes,
            "regions": len(rows), "rows_per_region": rows,
            "rows_per_block": buckets,
            "block_bytes": sum(buckets) * spec.row_nbytes}


class Run:
    """One process's run of one cell (``run.py`` makes one; the sweep and
    the calibration reuse the set-up for several windows)."""

    def __init__(self, cell: Cell, seed: int, *, devices,
                 shape: Optional[Sequence[int]] = None,
                 interpret: bool = False):
        self.cell = cell
        self.seed = int(seed)
        self.devices = list(devices)
        self.shape = shape
        self.interpret = interpret
        self.parts: Dict[str, float] = {}
        self.layout: Dict[str, Any] = {}

    # ------------------------------------------------------------------
    # set-up
    # ------------------------------------------------------------------

    def setup(self, schedules: Sequence[Sequence[traffic.Query]]) -> None:
        """Everything before the first window: cohort, upload, session,
        block commit and the warm-up of every shape ``schedules`` use."""
        import jax
        from repro.core.frontend import GridFrontend
        from repro.core.grid import GridSession

        cfg = self.cell.config
        t = time.perf_counter()
        self.cohort = cohort_mod.build(cfg, self.seed, self.shape)
        self.parts["generate_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.table = cohort_mod.table_of(self.cohort, cfg)
        # the reference draws the volumes again once the window has
        # closed: the host holds one copy of the cohort, the table's
        self.cohort.data = None
        self.parts["upload_s"] = time.perf_counter() - t
        t = time.perf_counter()
        mesh = jax.sharding.Mesh(np.asarray(self.devices), ("data",))
        self.session = GridSession(self.table, mesh=mesh,
                                   fold_interpret=self.interpret,
                                   **cfg["session"])
        self.frontend = GridFrontend(self.session, **cfg["frontend"])
        self.plans = traffic.PlanMaker(self.session, self.cell.mix)
        self.layout = describe_layout(self.session)
        self.parts["session_s"] = time.perf_counter() - t
        spec = self.table.column_spec("img", "data")
        self.features = int(np.prod(spec.shape))
        self.row_nbytes = int(spec.row_nbytes)

        regions = [self.table.region_rows(r) for r in self.table.regions]
        warm = self._warm_sets(regions, schedules)
        t = time.perf_counter()
        self._answer(self.plans.rows_plan(warm[0], self.cohort.ages))
        self.parts["commit_s"] = time.perf_counter() - t
        t = time.perf_counter()
        for rows in warm[1:]:
            self._answer(self.plans.rows_plan(rows, self.cohort.ages))
        self.parts["warm_s"] = time.perf_counter() - t

    def _warm_sets(self, regions: List[slice],
                   schedules: Sequence[Sequence[traffic.Query]]
                   ) -> List[np.ndarray]:
        """Row sets of the warm-up queries.  The first selects rows of
        every region (it commits every block); then one query per merge
        size the schedules need (the engine buckets partial counts to
        powers of two), each on fresh rows so it folds; then, where some
        query selects every row of a region whose row count is already
        its fold bucket, a query that does the same (the unmasked
        fold)."""
        engine = self.session.engine
        n_regions = len(regions)
        bucket = lambda k: 0 if k == 0 else (  # noqa: E731
            1 if k == 1 else engine._next_pow2(k))
        need = set()
        full = set()
        for sched in schedules:
            for q in sched:
                m = traffic.mask_of(q, self.cohort.ages, self.cohort.sexes)
                touched = 0
                for r, sl in enumerate(regions):
                    sel = int(m[sl].sum())
                    rows = sl.stop - sl.start
                    touched += sel > 0
                    if sel == rows and engine.bucket_rows(rows) == rows:
                        full.add(r)
                need.add(bucket(touched))
        # a tenth of each region's rows: above the session's compact-gather
        # threshold, so this query commits whole blocks
        sets = [np.concatenate([
            np.arange(sl.start, sl.start + max(1, (sl.stop - sl.start) // 10))
            for sl in regions])]
        need.discard(bucket(n_regions))
        offset = 1
        for b in sorted(need):
            k = 1 if b == 1 else (0 if b == 0 else b // 2 + 1)
            k = min(k, n_regions)
            sets.append(np.array([regions[r].start + offset
                                  % (regions[r].stop - regions[r].start)
                                  for r in range(k)], int))
            offset += 1
        for r in sorted(full)[:1]:
            sets.append(np.arange(regions[r].start, regions[r].stop))
        return sets

    def volumes(self) -> np.ndarray:
        """The cohort's volumes, drawn again from the seed."""
        return cohort_mod.build(self.cell.config, self.seed, self.shape).data

    def volume_blocks(self):
        """The same volumes, drawn again a block of rows at a time."""
        return cohort_mod.volume_blocks(self.cell.config, self.seed,
                                        self.shape)

    def _answer(self, plan):
        import jax

        res, _ = self.frontend.submit(plan).result()
        return jax.device_get(res)

    # ------------------------------------------------------------------
    # the window
    # ------------------------------------------------------------------

    def window(self, queries: Sequence[traffic.Query], seconds: float,
               keep: Sequence[int] = (), trace_dir: Optional[str] = None
               ) -> Dict[str, Any]:
        """Offer ``queries`` open loop for ``seconds``; wait for their
        answers (up to ``DRAIN_S`` past the close).  Returns latencies,
        lateness, the kept answers, the in-flight intervals and the
        counters' deltas.  With ``trace_dir`` the window is traced into
        that directory; the caller reduces the trace."""
        import jax
        from jax.profiler import TraceAnnotation

        plans = [self.plans.plan(q) for q in queries]
        n = len(queries)
        keep = set(keep)
        t_done = [math.inf] * n
        late = [0.0] * n
        reports: List[Any] = [None] * n
        kept: Dict[int, Any] = {}
        errors: Dict[int, str] = {}
        fetch_q: "queue.Queue" = queue.Queue()
        resolved = threading.Semaphore(0)
        fe, session = self.frontend, self.session
        fe_before = fe.stats.snapshot()
        sm_before = session.metrics.snapshot()
        compiles_before = session.engine.compile_count

        def fetch() -> None:
            while True:
                item = fetch_q.get()
                if item is None:
                    return
                i, fut = item
                try:
                    with TraceAnnotation("bench.fetch"):
                        res, rep = fut.result()
                        host = jax.device_get(res)
                    t_done[i] = time.perf_counter()
                    reports[i] = rep
                    if i in keep:
                        kept[i] = host
                except Exception as e:  # noqa: BLE001 - a failed query
                    errors[i] = repr(e)
                resolved.release()

        fetchers = [threading.Thread(target=fetch, daemon=True,
                                     name=f"bench-fetch-{k}")
                    for k in range(FETCH_THREADS)]
        for th in fetchers:
            th.start()

        if trace_dir is not None:
            # the harness's spans are TraceMe events (host tracer); the
            # Python tracer would record every call of every thread
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = TraceAnnotation("bench.window")
        window_span.__enter__()
        t0 = time.perf_counter()

        def generate() -> None:
            for i, q in enumerate(queries):
                due = t0 + q.t_due
                with TraceAnnotation("bench.generator.sleep"):
                    while True:
                        left = due - time.perf_counter()
                        if left <= 0:
                            break
                        time.sleep(left)
                late[i] = time.perf_counter() - due
                try:
                    with TraceAnnotation("bench.submit"):
                        fut = fe.submit(plans[i])
                    fut.add_done_callback(
                        lambda f, i=i: fetch_q.put((i, f)))
                except Exception as e:  # noqa: BLE001 - refused
                    errors[i] = repr(e)
                    resolved.release()

        gen = threading.Thread(target=generate, name="bench-generator")
        gen.start()
        gen.join()
        deadline = t0 + seconds + DRAIN_S
        got = 0
        while got < n:
            if not resolved.acquire(timeout=max(0.0, deadline
                                                - time.perf_counter())):
                break
            got += 1
        t_last = time.perf_counter()
        window_span.__exit__(None, None, None)
        inflight = [(q.t_due + late[i], t_done[i] - t0)
                    for i, q in enumerate(queries) if t_done[i] < math.inf]
        if trace_dir is not None:
            jax.profiler.stop_trace()
            host_memory("after stop_trace", trace_dir)
        for _ in fetchers:
            fetch_q.put(None)
        for th in fetchers:
            th.join(timeout=DRAIN_S)

        lat = [t_done[i] - (t0 + q.t_due) for i, q in enumerate(queries)]
        return {
            "t0": t0, "seconds": seconds, "latency_s": lat,
            "late_s": late, "t_done": t_done, "reports": reports,
            "kept": kept, "errors": errors, "unresolved": n - got,
            "drain_s": t_last - (t0 + seconds), "inflight": inflight,
            "fe": _delta(fe.stats.snapshot(), fe_before),
            "session": _delta(session.metrics.snapshot(), sm_before),
            "compiles": session.engine.compile_count - compiles_before,
            "device_bytes": session.blocks.stats.device_bytes,
        }

    def close(self) -> None:
        """Free the system's state (host table, device blocks, caches)."""
        self.frontend.close()
        self.session.close()
        for attr in ("frontend", "session", "plans", "table"):
            if hasattr(self, attr):
                delattr(self, attr)
        gc.collect()


def _delta(after: Any, before: Any) -> Dict[str, Any]:
    """Field-wise difference of two counter snapshots."""
    b = dataclasses.asdict(before)
    return {k: v - b.get(k, 0) for k, v in dataclasses.asdict(after).items()
            if isinstance(v, (int, float))}


# ----------------------------------------------------------------------
# one benchmark run
# ----------------------------------------------------------------------

def end_to_end(out: Dict[str, Any], setup_s: float,
               peak: int) -> Dict[str, Dict[str, Any]]:
    lat = out["latency_s"]
    window_end = out["t0"] + out["seconds"]
    done = sum(1 for t in out["t_done"] if t <= window_end)
    return {
        "query_p50_ms": {"value": nearest_rank(lat, 0.50) * 1e3,
                         "unit": "ms"},
        "query_p95_ms": {"value": nearest_rank(lat, 0.95) * 1e3,
                         "unit": "ms"},
        "queries_per_s": {"value": done / out["seconds"],
                          "unit": "queries/s"},
        "peak_hbm_gb": {"value": peak / 1e9, "unit": "GB"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }


def metric_context(run: Run, out: Dict[str, Any], peaks: Dict,
                   trace: Optional[Dict]) -> Any:
    answered = sum(1 for t in out["t_done"] if math.isfinite(t))
    return types.SimpleNamespace(
        fe=out["fe"], session=out["session"], compiles=out["compiles"],
        device_bytes=out["device_bytes"], reports=out["reports"],
        trace=trace, answered=answered, peaks=peaks,
        latency_s=out["latency_s"],
        features=run.features, itemsize=4, row_nbytes=run.row_nbytes,
        programs=tuple(run.cell.mix["programs"]), work=work)


def execute(cell: Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float, devices, shape=None, interpret: bool = False,
            tamper: Optional[Callable[[Run], None]] = None
            ) -> Dict[str, Any]:
    """One run: the result object of ``run.py``'s last line.  ``tamper``
    lets the tests break the system under the harness after set-up."""
    import jax

    dev0 = devices[0]
    peaks = peak_for(dev0.device_kind) if dev0.platform == "tpu" else {
        "hbm_bytes_per_s": float("nan")}
    run = Run(cell, seed, devices=devices, shape=shape, interpret=interpret)
    queries = traffic.schedule(cell.mix, cell.rate, seconds, seed)
    run.setup([queries])
    masks = np.stack([traffic.mask_of(q, run.cohort.ages, run.cohort.sexes)
                      for q in queries])
    picked = traffic.sample(queries, masks, int(cell.mix["sample"]), seed)
    if tamper is not None:
        tamper(run)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup_s = time.perf_counter() - t_start
    log(f"{cell.name}: seed {seed}, {len(queries)} queries due in "
        f"{seconds:g} s at {cell.rate:g}/s; set-up {setup_s:.3f} s "
        + json.dumps({k: round(v, 3) for k, v in run.parts.items()}))
    log("layout " + json.dumps(run.layout))
    host_memory("at the end of set-up")
    out = run.window(queries, seconds, keep=picked, trace_dir=trace_dir)
    peak = peak_bytes(devices)
    late = sorted(out["late_s"])
    log(f"generator lateness: p95 {nearest_rank(late, 0.95) * 1e3:.3f} ms, "
        f"max {(late[-1] if late else 0) * 1e3:.3f} ms; answers drained "
        f"{out['drain_s']:.3f} s after the close; {len(out['errors'])} "
        f"errors, {out['unresolved']} unresolved")
    for i, e in list(out["errors"].items())[:5]:
        log(f"query {i} failed: {e}")
    log(run.session.describe().replace("\n", "\n  "))
    run.close()
    host_memory("after run.close()")
    tr = None
    if trace:
        # read once the host table and the session are freed
        tr = tracefile.reduce_trace(trace_dir, out["inflight"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        host_memory("after reduce_trace")
    ctx = metric_context(run, out, peaks, tr)

    t = time.perf_counter()
    answers = [out["kept"].get(i) for i in picked]
    count, mean, var = reference.float64_stats_of_blocks(
        run.volume_blocks(), masks[picked])
    read = reference.readings(answers, count, mean, var)
    ref_s = time.perf_counter() - t
    failed = len(out["errors"]) + out["unresolved"]
    correct = reference.within(read, cell.limits) and failed == 0
    log(f"reference: {len(picked)} answers compared in {ref_s:.3f} s")

    if trace:
        metrics = {}
        for m in cell.metrics("per_layer"):
            v = load_metric(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        names = {m["name"] for m in cell.metrics("end_to_end")}
        metrics = {k: v for k, v in end_to_end(out, setup_s, peak).items()
                   if k in names}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(queries),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
        log("trace " + json.dumps({k: v for k, v in tr.items()
                                   if k not in ("device_ops", "idle_gaps")}))
    checks = {k: {"value": read[k], "limit": cell.limits[k]}
              for k in reference.NUMBERS}
    checks["failed_queries"] = {"value": failed, "limit": 0}
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    return result
