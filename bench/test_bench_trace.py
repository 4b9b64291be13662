"""The trace reducer on a short trace recorded on one TPU v5 lite by a
``--trace 1`` run of ``t1_mni152_1mm.subset_open`` (``testdata/trace``,
with that window's in-flight intervals and counters beside it)."""

import json
import os

import pytest

from bench import tracefile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                    "trace")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "window.json")) as f:
        window = json.load(f)
    with open(os.path.join(DATA, "inflight.json")) as f:
        inflight = [tuple(x) for x in json.load(f)]
    return window, inflight, tracefile.reduce_trace(DATA, inflight)


def _busy_by_sweep(path):
    """Device busy time inside the window, by a plain sweep over every
    operation event (independent of the reducer's interval union)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(tracefile.find_xplane(path))
    lo = hi = None
    events = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tracefile.WINDOW_SPAN:
                    lo, hi = int(ev.start_ns), int(ev.end_ns)
                elif (plane.name.startswith("/device:TPU:")
                      and line.name == "XLA Ops"):
                    events.append((int(ev.start_ns), int(ev.end_ns)))
    points = sorted([(max(s, lo), 1) for s, e in events if e > lo and s < hi]
                    + [(min(e, hi), -1) for s, e in events
                       if e > lo and s < hi])
    busy = depth = 0
    last = lo
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy / 1e9, (hi - lo) / 1e9


def test_idle_share_matches_a_plain_sweep(recorded):
    _, _, tr = recorded
    busy, window = _busy_by_sweep(DATA)
    assert tr["window_s"] == pytest.approx(window, abs=1e-9)
    assert tr["busy_s"] == pytest.approx(busy, abs=1e-6)
    assert 0 < tr["busy_s"] < tr["window_s"]


def test_one_kernel_event_per_fold(recorded):
    window, _, tr = recorded
    assert tr["kernel_events"] == window["session"]["partials_folded"] > 0
    assert 0 < tr["kernel_s"] <= tr["busy_s"]
    assert tr["merge_events"] > 0 and 0 < tr["merge_s"] < tr["busy_s"]
    assert tr["device_ops"][0][0].startswith("%fused_fold_pallas")


def test_idle_gaps_are_named_by_harness_spans(recorded):
    _, _, tr = recorded
    names = {"bench.generator.sleep", "bench.fetch", "bench.submit",
             "program"}
    assert 0 < len(tr["idle_gaps"]) <= 10
    assert all(name in names for name, _ in tr["idle_gaps"])
    lengths = [s for _, s in tr["idle_gaps"]]
    assert lengths == sorted(lengths, reverse=True)
    assert sum(lengths) <= tr["window_s"] - tr["busy_s"] + 1e-9
