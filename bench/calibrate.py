#!/usr/bin/env python3
"""Readings that the correctness limits are set from, and the control.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 4

For each seed, in one process: the cell's whole run path (cohort, set-up,
a short window at the cell's own rate, the float64 reference over the
sampled answers) gives the program's readings; then the control, the
reference computed on the chip one precision step below the
configuration's float32 (``reference.control_stats``: the values in
bfloat16, summed in float32), is compared with the same float64 reference
over the same sample.  Each seed prints one JSON line; the last line
holds the largest program reading and the smallest control reading of
each compared number.  ``bench/run.py`` never runs the control.  Needs a
TPU.  The process's host memory grows with each seed (a peak of 32.5 GiB
after three 2 mm seeds on a 40 GiB TPU v5e host), so give each process
two or three seeds.
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


def one_seed(cell, seed: int, seconds: float, devices) -> dict:
    import numpy as np

    from bench import harness, reference, traffic

    t = time.perf_counter()
    queries = traffic.schedule(cell.mix, cell.rate, seconds, seed)
    run = harness.Run(cell, seed, devices=devices)
    run.setup([queries])
    masks = np.stack([traffic.mask_of(q, run.cohort.ages, run.cohort.sexes)
                      for q in queries])
    picked = traffic.sample(queries, masks, int(cell.mix["sample"]), seed)
    out = run.window(queries, seconds, keep=picked)
    run.close()
    data = run.volumes()
    sel = masks[picked]
    count, mean, var = reference.float64_stats(data, sel)
    program = reference.readings([out["kept"].get(i) for i in picked],
                                 count, mean, var)
    control = reference.control_readings(
        *reference.control_stats(data, sel), count, mean, var)
    return {"seed": seed, "answers": len(picked),
            "rows": [int(r) for r in sel.sum(axis=1)],
            "failed": len(out["errors"]) + out["unresolved"],
            "program": program, "control": control,
            "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.Cell.find(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache(jax)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        row = one_seed(cell, seed, args.seconds,
                       devices[:int(cell.workload["chips"])])
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = rows[0]["program"].keys()
    print(json.dumps({
        "lower": {k: max(r["program"][k] for r in rows) for k in keys},
        "upper": {k: min(r["control"][k] for r in rows) for k in keys},
        "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
