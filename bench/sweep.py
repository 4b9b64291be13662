#!/usr/bin/env python3
"""The knee sweep: one process steps a cell's traffic through offered
rates, to find the highest rate the system sustains.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 8 \
        --rates 4,8,12,16,24

Set-up is paid once (the warm-up covers every rate's schedule); then each
rate gets one window of ``--seconds``.  Each line of standard output is
one rate's JSON: offered and completed rates, p50/p95 latency, answers
still open at the close, and how late the generator ran.  The knee is the
highest rate whose completed rate keeps up with the offered one and whose
answers all arrive within a p95 or so of the close; the cell's fixed
rate (``bench/cells/<cell>.json``) is about four fifths of it.  Needs a
TPU, like ``run.py``.
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
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)

    from bench import harness, traffic

    cell = harness.Cell.find(args.workload)
    import jax

    devices = jax.devices()
    chips = int(cell.workload["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("sweep: needs a TPU with the cell's chips", file=sys.stderr)
        return 2
    harness.use_compile_cache(jax)
    rates = [float(r) for r in args.rates.split(",")]
    scheds = [traffic.schedule(cell.mix, r, args.seconds, args.seed)
              for r in rates]
    run = harness.Run(cell, args.seed, devices=devices[:chips])
    run.setup(scheds)
    harness.log(f"sweep set-up {time.perf_counter() - T_START:.3f} s "
                + json.dumps(run.parts))
    for rate, sched in zip(rates, scheds):
        out = run.window(sched, args.seconds)
        end = out["t0"] + out["seconds"]
        done = sum(1 for t in out["t_done"] if t <= end)
        lat = out["latency_s"]
        print(json.dumps({
            "rate_offered": len(sched) / args.seconds,
            "rate_completed": done / args.seconds,
            "p50_ms": harness.nearest_rank(lat, 0.5) * 1e3,
            "p95_ms": harness.nearest_rank(lat, 0.95) * 1e3,
            "open_at_close": len(sched) - done,
            "drain_s": out["drain_s"],
            "late_p95_ms": harness.nearest_rank(out["late_s"], 0.95) * 1e3,
            "errors": len(out["errors"]), "compiles": out["compiles"],
        }), flush=True)
    harness.log(f"peak HBM {harness.peak_bytes(devices[:chips]) / 1e9:.3f} GB")
    run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
