import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: for the
production meshes (16×16 single-pod, 2×16×16 multi-pod) each cell's step
function must lower, SPMD-partition and compile; we record
``memory_analysis()`` (fits?), ``cost_analysis()`` (FLOPs/bytes) and the
collective schedule parsed from the optimized HLO.

Accounting: XLA counts scan bodies once, so the scanned (deployed) program
under-reports flops/bytes/collectives.  Single-pod cells therefore also
compile the tiny unrolled *probe* variants (see launch/probes.py) and
report scan-corrected totals — these feed EXPERIMENTS.md §Roofline.

Results are cached as JSON under ``artifacts/dryrun/`` (one file per cell);
reruns are incremental.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch all|<id,...>] [--shape all|<name,...>] \
        [--mesh single,multi] [--force] [--no-probes] [--out DIR]
"""

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.probes import corrected, make_probe_plan
from repro.launch.roofline import (
    collective_bytes_from_hlo,
    derive_terms,
    model_flops,
)
from repro.launch.shapes import SHAPES, cell_applicable, input_specs
from repro.launch.steps import CellBuilder


def compile_cell(cfg, shape: str, mesh, kind: str) -> Dict:
    """Lower+compile one configuration; return raw measurements."""
    t0 = time.perf_counter()
    builder = CellBuilder(cfg, mesh, kind)
    specs = input_specs(cfg, shape)
    fn, args, shardings, donate = builder.build(specs)
    jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
    lowered = jitted.lower(*args)
    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_rec = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes")
        if hasattr(mem, k)
    }
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire_bytes": coll["wire_bytes"],
        "coll_by_op": coll["by_op"],
        "coll_count": coll["count"],
        "memory": mem_rec,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: str,
             force: bool = False, probes: bool = True) -> Dict:
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    ok, reason = cell_applicable(cfg, shape)
    record: Dict = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    if not ok:
        record.update(status="skipped", reason=reason)
        _write(path, record)
        return record

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    spec = SHAPES[shape]
    try:
        main = compile_cell(cfg, shape, mesh, spec.kind)
        mem_rec = main["memory"]
        per_dev_bytes = (
            mem_rec.get("argument_size_in_bytes", 0)
            + mem_rec.get("temp_size_in_bytes", 0)
            - mem_rec.get("alias_size_in_bytes", 0)
        )
        record.update(
            status="ok",
            devices=mesh.size,
            raw=main,
            per_device_bytes=per_dev_bytes,
            fits_v5e=bool(per_dev_bytes < 16e9),
        )

        if probes and mesh_name == "single":
            probe_a, probe_bs = make_probe_plan(cfg)
            a = compile_cell(probe_a, shape, mesh, spec.kind)
            bs = [(pb, compile_cell(pb.cfg, shape, mesh, spec.kind))
                  for pb in probe_bs]
            corr = corrected(a, bs)
            terms = derive_terms(corr["flops"], corr["bytes"],
                                 corr["wire_bytes"])
            mf = model_flops(cfg, spec)
            record.update(
                probes={
                    "a": {k: a[k] for k in ("flops", "bytes", "wire_bytes",
                                            "compile_s")},
                    "bodies": {
                        pb.label: {
                            "flops": m["flops"] - a["flops"],
                            "bytes": m["bytes"] - a["bytes"],
                            "wire_bytes": m["wire_bytes"] - a["wire_bytes"],
                            "n_full": pb.n_full,
                        } for pb, m in bs
                    },
                },
                corrected={k: corr[k] for k in ("flops", "bytes",
                                                "wire_bytes")},
                roofline={
                    "compute_s": terms.compute_s,
                    "memory_s": terms.memory_s,
                    "collective_s": terms.collective_s,
                    "dominant": terms.dominant,
                    "bound_s": terms.bound_s,
                    "compute_fraction": terms.compute_fraction(),
                    "model_flops_total": mf,
                    "model_flops_per_device": mf / mesh.size,
                    "useful_flops_ratio":
                        (mf / mesh.size) / max(corr["flops"], 1e-30),
                },
            )
    except Exception as e:  # a failing cell is a bug — record it loudly
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    _write(path, record)
    return record


def _write(path, record):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                t0 = time.perf_counter()
                rec = run_cell(arch, shape, mesh_name, args.out,
                               force=args.force, probes=not args.no_probes)
                dt = time.perf_counter() - t0
                status = rec["status"]
                extra = ""
                if status == "ok":
                    n_ok += 1
                    if "roofline" in rec:
                        r = rec["roofline"]
                        extra = (f"dom={r['dominant']:10s} "
                                 f"frac={r['compute_fraction']:.3f} "
                                 f"mem={rec['per_device_bytes']/1e9:6.2f}GB")
                    else:
                        extra = f"mem={rec['per_device_bytes']/1e9:6.2f}GB/dev"
                elif status == "skipped":
                    n_skip += 1
                    extra = rec["reason"][:60]
                else:
                    n_err += 1
                    extra = rec["error"][:140]
                print(f"[{status:7s}] {arch:18s} {shape:12s} {mesh_name:6s} "
                      f"({dt:6.1f}s) {extra}", flush=True)
    print(f"\nDRYRUN SUMMARY: ok={n_ok} skipped={n_skip} errors={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
