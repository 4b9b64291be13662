"""The CI perf gate (benchmarks/check_regression.py): passes in-tolerance
metrics, FAILS on an injected >tolerance regression, and never passes
vacuously when a required artifact or metric is missing.

The injected-regression cases here are the same demonstration the PR
description quotes:

    python -m benchmarks.check_regression --bench-dir <dir-with-bad-json>
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.check_regression import check_metric, run_gate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write(path, payload):
    path.write_text(json.dumps(payload))


@pytest.fixture
def baselines(tmp_path):
    p = tmp_path / "perf_baselines.json"
    write(p, {
        "default_tolerance": 0.25,
        "metrics": {
            "fake": {
                "speedup": {"baseline": 4.0, "direction": "higher"},
                "flop_ratio": {"baseline": 0.66, "direction": "lower"},
                "probe": {"baseline": 1.0, "direction": "higher",
                          "optional": True},
            },
        },
    })
    return p


def emit(tmp_path, **metrics):
    write(tmp_path / "BENCH_fake.json",
          {"bench": "fake", "elapsed_us": 1,
           "speedup": 4.1, "flop_ratio": 0.65, "probe": 1.2, **metrics})


class TestPerfGate:
    def test_passes_within_tolerance(self, tmp_path, baselines):
        emit(tmp_path)
        ok, lines = run_gate(str(tmp_path), str(baselines))
        assert ok, lines

    def test_fails_on_injected_regression(self, tmp_path, baselines):
        # >25% below the 4.0 baseline: 4.0 * 0.75 = 3.0 is the floor
        emit(tmp_path, speedup=2.9)
        ok, lines = run_gate(str(tmp_path), str(baselines))
        assert not ok
        assert any("REGRESSION" in ln and "speedup" in ln for ln in lines)

    def test_boundary_is_not_a_regression(self, tmp_path, baselines):
        emit(tmp_path, speedup=3.0)          # exactly the 25% floor
        ok, _ = run_gate(str(tmp_path), str(baselines))
        assert ok

    def test_lower_direction_gates_increases(self, tmp_path, baselines):
        # flop RATIO regresses by going UP: 0.66 * 1.25 = 0.825 ceiling
        emit(tmp_path, flop_ratio=0.9)
        ok, lines = run_gate(str(tmp_path), str(baselines))
        assert not ok
        assert any("REGRESSION" in ln and "flop_ratio" in ln
                   for ln in lines)

    def test_missing_artifact_fails(self, tmp_path, baselines):
        ok, lines = run_gate(str(tmp_path), str(baselines))
        assert not ok
        assert any("MISSING" in ln for ln in lines)

    def test_missing_metric_fails(self, tmp_path, baselines):
        write(tmp_path / "BENCH_fake.json",
              {"bench": "fake", "flop_ratio": 0.6, "probe": 1.0})
        ok, lines = run_gate(str(tmp_path), str(baselines))
        assert not ok

    def test_optional_probe_zero_is_skipped(self, tmp_path, baselines):
        # the merge probe reports 0 on one device, where there is no
        # tree to time — that is "no data", not a regression
        emit(tmp_path, probe=0.0)
        ok, lines = run_gate(str(tmp_path), str(baselines))
        assert ok, lines

    def test_optional_probe_regression_still_fails(self, tmp_path,
                                                   baselines):
        emit(tmp_path, probe=0.5)            # real data, below tolerance
        ok, _ = run_gate(str(tmp_path), str(baselines))
        assert not ok

    def test_check_metric_directions(self):
        assert check_metric("m", 3.9, 4.0, "higher", 0.25)[0]
        assert not check_metric("m", 2.9, 4.0, "higher", 0.25)[0]
        assert check_metric("m", 0.8, 0.66, "lower", 0.25)[0]
        assert not check_metric("m", 0.9, 0.66, "lower", 0.25)[0]
        assert not check_metric("m", 1.0, 1.0, "sideways", 0.25)[0]

    def test_committed_baselines_parse_and_cover_group_by(self):
        from benchmarks.check_regression import DEFAULT_BASELINES
        spec = json.load(open(DEFAULT_BASELINES))
        assert "group_by" in spec["metrics"]
        assert "grouped_speedup_vs_loop" in spec["metrics"]["group_by"]
        for bench, metrics in spec["metrics"].items():
            for name, m in metrics.items():
                assert m.get("direction") in ("higher", "lower"), (bench,
                                                                   name)
                assert float(m["baseline"]) > 0


def _merge_gate(devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_group_by", "--merge-gate"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)


class TestMergeGate:
    """The tree-merge gate CI runs on its multi-device job: it times the
    tree against the funnel where there are devices to tree over, and
    fails loudly where there is one."""

    def test_times_and_gates_on_several_devices(self):
        proc = _merge_gate(4)
        assert "x 4 dev" in proc.stdout, proc.stdout + proc.stderr
        assert "group_by.merge_tree_speedup:" in proc.stdout
        assert proc.returncode in (0, 1)     # the verdict is timing's

    def test_fails_with_one_device(self):
        proc = _merge_gate(1)
        assert proc.returncode == 1
        assert "merge gate FAILED" in proc.stdout
