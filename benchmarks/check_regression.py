"""Perf gate: compare emitted ``BENCH_*.json`` headline metrics against the
committed baselines and FAIL on a regression beyond tolerance.

Baselines live in ``benchmarks/perf_baselines.json``::

    {
      "default_tolerance": 0.25,
      "metrics": {
        "fold_engine": {
          "warm_speedup_vs_refold": {"baseline": 6.0, "direction": "higher"}
        },
        ...
      }
    }

Every gated metric is a *ratio* (speedup vs an in-run baseline), so it
self-normalizes across machines — absolute wall clocks are deliberately
not gated.  ``direction: "higher"`` fails when
``value < baseline * (1 - tolerance)``; ``"lower"`` fails when
``value > baseline * (1 + tolerance)``.  A missing artifact or metric is a
FAILURE (the gate must not pass vacuously) unless the entry sets
``"optional": true``.

    PYTHONPATH=src python -m benchmarks.check_regression
    PYTHONPATH=src python -m benchmarks.check_regression --bench-dir out/

Exit code 0 = all gated metrics within tolerance; 1 = regression (or
missing required data).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Tuple

DEFAULT_BASELINES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "perf_baselines.json")


def check_metric(name: str, value: float, baseline: float,
                 direction: str, tolerance: float) -> Tuple[bool, str]:
    """One gated metric: ``(ok, human-readable verdict line)``."""
    if direction == "higher":
        floor = baseline * (1.0 - tolerance)
        ok = value >= floor
        bound = f">= {floor:.3f}"
    elif direction == "lower":
        ceil = baseline * (1.0 + tolerance)
        ok = value <= ceil
        bound = f"<= {ceil:.3f}"
    else:
        return False, f"{name}: unknown direction {direction!r}"
    verdict = "ok" if ok else "REGRESSION"
    return ok, (f"{name}: {value:.3f} (baseline {baseline:.3f}, "
                f"need {bound}) {verdict}")


def run_gate(bench_dir: str, baselines_path: str) -> Tuple[bool, List[str]]:
    with open(baselines_path) as f:
        spec = json.load(f)
    default_tol = float(spec.get("default_tolerance", 0.25))
    lines: List[str] = []
    ok_all = True
    for bench, metrics in sorted(spec.get("metrics", {}).items()):
        path = os.path.join(bench_dir, f"BENCH_{bench}.json")
        if not os.path.exists(path):
            if all(m.get("optional") for m in metrics.values()):
                lines.append(f"BENCH_{bench}.json: missing (optional), "
                             f"skipped")
                continue
            lines.append(f"BENCH_{bench}.json: MISSING (required artifact)")
            ok_all = False
            continue
        with open(path) as f:
            payload = json.load(f)
        for metric, m in sorted(metrics.items()):
            label = f"{bench}.{metric}"
            if metric not in payload:
                if m.get("optional"):
                    lines.append(f"{label}: missing (optional), skipped")
                    continue
                lines.append(f"{label}: MISSING from artifact")
                ok_all = False
                continue
            value = float(payload[metric])
            if m.get("optional") and value == 0.0:
                # optional probes report 0 when their environment (e.g.
                # several devices) is unavailable — not a regression
                lines.append(f"{label}: 0.0 (optional probe unavailable), "
                             f"skipped")
                continue
            ok, line = check_metric(
                label, value, float(m["baseline"]),
                m.get("direction", "higher"),
                float(m.get("tolerance", default_tol)))
            lines.append(line)
            ok_all = ok_all and ok
    return ok_all, lines


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--bench-dir", default=".",
                        help="directory holding the emitted BENCH_*.json")
    parser.add_argument("--baselines", default=DEFAULT_BASELINES,
                        help="committed baseline/tolerance file")
    args = parser.parse_args()
    ok, lines = run_gate(args.bench_dir, args.baselines)
    print("perf gate:", args.baselines)
    for line in lines:
        print(" ", line)
    if not ok:
        print("perf gate FAILED: headline metric regressed beyond tolerance")
        return 1
    print("perf gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
