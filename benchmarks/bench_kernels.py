"""Kernel microbench: allclose vs oracle + op accounting.

The kernels compile on a TPU and run in interpret mode elsewhere (the
SSD scan, which has no TPU lowering, is interpreted everywhere).
Wall-clock on CPU interpret mode is NOT a TPU perf signal; what this bench
certifies is (1) numeric agreement on production-shaped tiles, (2) the
analytic FLOPs/bytes per call that the roofline model uses for the kernels'
VMEM tiling story.

The ``fused_fold`` section gates the tentpole's one-HBM-pass contract with
modeled ratios (stable across machines, unlike interpret wall clock):

- ``fused_fold_speedup_grouped`` / ``_ungrouped`` — bytes XLA's own
  ``cost_analysis`` measures for the reference chunk-scan fold of the CSE
  pool, over the kernel's analytic one-pass HBM bytes for the same block.
  > 1 means the kernel genuinely reduces chunk bytes-read per fold;
- ``fused_fold_roofline_bw_frac`` — ``memory_s / bound_s`` from
  ``launch/roofline.py`` on the kernel's analytic FLOPs/bytes: 1.0 says
  the kernel is bandwidth-bound (intensity far below the ridge), i.e. a
  perfectly streaming kernel runs at peak HBM bandwidth.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssm_scan.ops import ssd_scan
from repro.kernels.streaming_stats.ops import streaming_stats
from repro.kernels.streaming_stats.ref import streaming_stats_ref

#: the kernels compile on a TPU; every other backend runs the interpreter
INTERPRET = jax.default_backend() != "tpu"


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def _fused_fold_section(rng, rows):
    """Fused fold kernel: oracle agreement + one-HBM-pass ratio metrics."""
    from repro.core.mapreduce import MapReduceEngine
    from repro.core.stats import (
        FusedProgram, GroupedProgram, MeanProgram, MomentsProgram,
        VarianceProgram)
    from repro.kernels.fused_fold import (
        fused_fold, fused_fold_numpy, kernel_flops, kernel_hbm_bytes)
    from repro.launch.roofline import derive_terms
    from repro.utils import make_mesh

    R, shape, eta, G = 256, (64, 48), 64, 7
    F = int(np.prod(shape))
    names = ("count", "s1", "s2", "s3", "s4")

    x = rng.normal(size=(R,) + shape).astype(np.float32)
    m = rng.random(R) > 0.2
    g = rng.integers(0, G, R).astype(np.int32)
    got = fused_fold(jnp.asarray(x), jnp.asarray(m), jnp.asarray(g),
                     num_groups=G, interpret=INTERPRET)
    want = fused_fold_numpy(x, m, g, num_groups=G)
    err = max(float(np.abs(np.asarray(got[n], np.float64)
                           - want[n]).max()) for n in names)
    us = _time(lambda a, b, c: fused_fold(a, b, c, num_groups=G,
                                          interpret=INTERPRET),
               jnp.asarray(x), jnp.asarray(m), jnp.asarray(g))

    # measured XLA fold bytes (cost_analysis of the reference chunk scan)
    # vs the kernel's analytic one-pass bytes, grouped and ungrouped
    eng = MapReduceEngine(make_mesh((1,), ("data",)))
    cse = (MeanProgram(), VarianceProgram(), MomentsProgram())
    kernel_bytes = kernel_hbm_bytes(R, F, 4, names, num_groups=G)
    xla_g = eng.fold_cost(GroupedProgram(FusedProgram(cse), num_groups=G),
                          R, shape, jnp.float32, eta, masked=True, groups=G)
    xla_u = eng.fold_cost(FusedProgram(cse), R, shape, jnp.float32, eta,
                          masked=True)
    speedup_g = (xla_g["bytes"] / kernel_bytes
                 if xla_g["bytes"] and kernel_bytes else 0.0)
    speedup_u = (xla_u["bytes"] / kernel_hbm_bytes(R, F, 4, names)
                 if xla_u["bytes"] else 0.0)

    terms = derive_terms(kernel_flops(R, F, names, num_groups=G),
                         kernel_bytes, 0.0)
    bw_frac = terms.memory_s / terms.bound_s if terms.bound_s else 0.0

    rows.append((f"fused_fold_g{G}_256x64x48", us,
                 f"maxerr={err:.1e};xla_bytes={xla_g['bytes']:.2e};"
                 f"kernel_bytes={kernel_bytes:.2e};"
                 f"bytes_ratio={speedup_g:.2f};"
                 f"roofline={terms.dominant}"))
    return {
        "fused_fold_speedup_grouped": speedup_g,
        "fused_fold_speedup_ungrouped": speedup_u,
        "fused_fold_roofline_bw_frac": bw_frac,
    }


def run(verbose: bool = True):
    rng = np.random.default_rng(0)
    rows = []

    # streaming stats: one map-task chunk (eta=50 rows of 1MB fp32)
    R, F = 50, 262_144
    x = jnp.asarray(rng.normal(size=(R, F)).astype(np.float32))
    m = jnp.ones((R,), bool)
    s, _, c = streaming_stats(x, m, interpret=INTERPRET)
    rs, _, rc = streaming_stats_ref(x, m)
    err = float(jnp.abs(s - rs).max())
    us = _time(lambda a, b: streaming_stats(a, b, impl="ref"), x, m)
    rows.append(("streaming_stats_eta50_1MBrows", us,
                 f"maxerr={err:.1e};bytes={x.nbytes/1e6:.0f}MB;"
                 f"flops={2*R*F:.2e}"))

    # pallas map phase wired into the grid: GridSession.run(impl="pallas")
    # vs the jnp reference fold over the same 4-region table
    from repro.core.grid import GridSession
    from repro.core.stats import MeanProgram
    from repro.core.table import make_mip_table

    t = make_mip_table(payload_shape=(16, 16),
                       presplit_keys=["g1", "g2", "g3"])
    gk = [f"g{i % 4}x{i:04d}" for i in range(64)]
    t.upload(sorted(gk), {
        "img": {"data": rng.normal(size=(64, 16, 16)).astype(np.float32)},
        "idx": {"size": rng.integers(6_000_000, 20_000_001, 64)}})
    sess = GridSession(t, default_eta=8, fold_interpret=True)
    ref_res, _ = sess.run(MeanProgram(), impl="ref")
    pal_res, _ = sess.run(MeanProgram(), impl="pallas")
    err = float(jnp.abs(jnp.asarray(pal_res) - jnp.asarray(ref_res)).max())
    sess.blocks.clear_partials()

    def grid_pallas():
        sess._results.clear()
        sess.blocks.clear_partials()
        return sess.run(MeanProgram(), impl="pallas")[0]
    us = _time(lambda: grid_pallas())
    rows.append(("grid_map_phase_pallas_64x16x16", us,
                 f"maxerr_vs_ref={err:.1e};regions={len(t.regions)}"))

    # flash attention: one 128-block tile at head_dim 128
    B, H, S, D = 1, 4, 256, 128
    q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    out = flash_attention(q, k, v, scale=D ** -0.5, interpret=INTERPRET)
    ref = attention_ref(q, k, v, scale=D ** -0.5)
    err = float(jnp.abs(out - ref).max())
    us = _time(lambda *a: flash_attention(*a, scale=D ** -0.5, impl="ref"),
               q, k, v)
    rows.append(("flash_attention_b1h4s256d128", us,
                 f"maxerr={err:.1e};flops={4*B*H*S*S*D:.2e}"))

    # ssd scan: mamba2-native dims, one chunk stream; interpreted on every
    # backend, since Pallas has no TPU lowering for the kernel's cumsum
    B2, L, H2, P, N = 1, 256, 4, 64, 64
    xs = jnp.asarray(rng.normal(size=(B2, L, H2, P)).astype(np.float32)) * .5
    a = jnp.asarray(rng.uniform(0.8, 0.999, (B2, L, H2)).astype(np.float32))
    Bm = jnp.asarray(rng.normal(size=(B2, L, N)).astype(np.float32)) * .3
    Cm = jnp.asarray(rng.normal(size=(B2, L, N)).astype(np.float32)) * .3
    y, s_fin = ssd_scan(xs, a, Bm, Cm, chunk=128)
    y_ref, _ = ssd_scan(xs, a, Bm, Cm, impl="ref")
    err = float(jnp.abs(y - y_ref).max())
    us = _time(lambda *z: ssd_scan(*z, impl="ref"), xs, a, Bm, Cm)
    rows.append(("ssd_scan_l256_h4_p64_n64", us,
                 f"maxerr={err:.1e};state={H2*P*N*4}B"))

    metrics = _fused_fold_section(rng, rows)

    if verbose:
        for name, us, derived in rows:
            print(f"{name},{us:.0f},{derived}")
        for k, v in metrics.items():
            print(f"{k}={v:.2f}")
    return {"rows": rows, **metrics}


if __name__ == "__main__":
    run()
