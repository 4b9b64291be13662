#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Each run is one process on the cell's chips.  It draws the cell's cohort
from ``--seed``, uploads it, commits its blocks and warms every shape the
window will use (all of that is ``setup_s``), offers the cell's traffic
open loop through ``GridFrontend.submit`` for ``--seconds``, and then
compares a sample of the answers with a float64 reference.  The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each compared number beside its limit); the last lines
of standard error are the same checks.  With ``--trace 0`` the metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones,
read from a profiler trace of the window and the system's counters.

A run needs a TPU: without one, or with fewer chips than the cell asks
for, it exits 2 and prints no result.  (The tests drive the same path on
the CPU at a tiny volume through ``harness.execute``.)

Adding to the benchmark takes new files and entries only:

- a configuration: ``bench/configs/<name>.json`` (sizes, session and
  frontend settings, ``source``, ``reduced``, ``assumed``) and an entry
  under ``configs`` in ``BENCHMARK.json``;
- a traffic mix: ``bench/mixes/<name>.json``, parameters read by the one
  generator in ``bench/traffic.py``;
- a cell: an entry under ``workloads`` and ``bench/cells/<cell>.json``
  with its fixed ``rate_per_s`` (found once by ``bench/sweep.py``);
- a per-layer metric: ``bench/metrics/<name>.py`` defining
  ``read(ctx) -> float | None`` and an entry under ``per_layer``.

``benchmarks/`` (the ``bench_*.py`` files and ``BENCH_*.json``) stays
what it is: a CPU tripwire for CI at toy sizes, not a measurement of the
system's speed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
# the TPU runtime's logs would go to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.Cell.find(args.workload)
    import jax

    devices = jax.devices()
    chips = int(cell.workload["chips"])
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devices[0].platform!r} "
              f"devices", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips; JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    harness.use_compile_cache(jax)
    result = harness.execute(cell, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START,
                             devices=devices[:chips])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
