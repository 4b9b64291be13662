"""The plain reference, its lower-precision control, and the comparison
that decides ``correct``.

Reference: for each compared answer, the float64 count, voxel-wise mean
and population variance of the rows its filter selects, summed over the
benchmark's own copy of the volumes (never the system's table).  One
pass over the cohort serves every compared answer: a thread pool splits
the voxels, each slice is converted to float64 once, and each answer
sums the rows it selects.  The run draws the volumes again block by block
(``cohort.volume_blocks``), so the pass never holds the whole cohort.

Control: the same sums computed on the accelerator one precision step
below the configurations', the step a later change would be tempted to
take.  The configurations fold in float32 (the fused kernel's row sum
adds float32 on the VPU), so the control takes each value in bfloat16,
as a fold streaming bfloat16 blocks would, and sums in float32: ``w @ b``
and ``w @ (b * b)`` with ``b`` the bfloat16 rounding of each value.  The
products with 0/1 weights are exact at ``Precision.HIGHEST``, so it gives
the same numbers on every backend.

An empty selection answers the monoid identity: count 0, mean 0,
variance 0.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

#: voxels per reference slice (per thread task): small enough that a
#: slice's float64 temporaries are reused from the heap, not faulted in
SLICE = 1 << 12

#: the compared numbers; each cell's limits are in ``cells/<cell>.json``
#: (PERF.md gives the readings each limit was set from)
NUMBERS = ("count_err", "mean_err", "var_err")


def float64_stats(data: np.ndarray, masks: np.ndarray,
                  threads: Optional[int] = None
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(count [K], mean [K, F], var [K, F])`` in float64 for the ``K``
    selections ``masks [K, N]`` over ``data [N, ...]``."""
    return float64_stats_of_blocks([(0, data)], masks, threads)


def float64_stats_of_blocks(blocks: Iterable[Tuple[int, np.ndarray]],
                            masks: np.ndarray,
                            threads: Optional[int] = None
                            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """:func:`float64_stats` over the volumes given as ``(first_row,
    rows)`` blocks that cover them in order: each slice of a block's
    voxels is converted to float64 once, and each selection adds its own
    rows of it."""
    masks = np.asarray(masks, bool)
    count = masks.sum(axis=1).astype(np.float64)
    s1 = s2 = None
    with ThreadPoolExecutor(threads or min(8, os.cpu_count() or 1)) as pool:
        for first, block in blocks:
            x = block.reshape(len(block), -1)
            feats = x.shape[1]
            if s1 is None:
                s1 = np.zeros((len(masks), feats), np.float64)
                s2 = np.zeros((len(masks), feats), np.float64)
            rows = [np.flatnonzero(m) for m in masks[:, first:first + len(x)]]
            used = np.unique(np.concatenate(rows + [np.zeros(0, int)]))
            pos = np.searchsorted(used, np.arange(len(x)))
            local = [pos[r] for r in rows]

            def one(lo: int) -> None:
                hi = min(lo + SLICE, feats)
                xs = x[used, lo:hi].astype(np.float64)
                x2 = xs * xs
                for k, r in enumerate(local):
                    if len(r):
                        s1[k, lo:hi] += xs[r].sum(axis=0)
                        s2[k, lo:hi] += x2[r].sum(axis=0)

            if len(used):
                list(pool.map(one, range(0, feats, SLICE)))
    safe = np.maximum(count, 1.0)[:, None]
    mean = s1 / safe
    var = s2 / safe - mean * mean
    return count, mean, var


def control_stats(data: np.ndarray, masks: np.ndarray,
                  rows_per_step: int = 32
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The reference's sums on the default JAX device over the values
    rounded to bfloat16: ``(count, mean, var)`` as float32 host arrays."""
    import jax
    import jax.numpy as jnp

    n_rows = data.shape[0]
    x_host = data.reshape(n_rows, -1)
    hp = jax.lax.Precision.HIGHEST

    @jax.jit
    def step(acc, xb, wb):
        s1, s2 = acc
        # bfloat16 rounding kept in float32 (reduce_precision, which the
        # compiler does not fold away as it may a convert pair)
        b = jax.lax.reduce_precision(xb, exponent_bits=8, mantissa_bits=7)
        return (s1 + jnp.dot(wb, b, precision=hp),
                s2 + jnp.dot(wb, b * b, precision=hp))

    k = masks.shape[0]
    w = masks.astype(np.float32)
    acc = (jnp.zeros((k, x_host.shape[1]), jnp.float32),
           jnp.zeros((k, x_host.shape[1]), jnp.float32))
    for lo in range(0, n_rows, rows_per_step):
        hi = min(lo + rows_per_step, n_rows)
        xb = np.zeros((rows_per_step, x_host.shape[1]), np.float32)
        wb = np.zeros((k, rows_per_step), np.float32)
        xb[:hi - lo] = x_host[lo:hi]
        wb[:, :hi - lo] = w[:, lo:hi]
        acc = step(acc, jnp.asarray(xb), jnp.asarray(wb))
    s1, s2 = (np.asarray(a) for a in acc)
    count = w.sum(axis=1)
    n = np.maximum(count, 1.0)[:, None]
    mean = s1 / n
    var = np.maximum(s2 / n - mean * mean, 0.0)
    return count, mean, var


def answer_parts(answer) -> Tuple[float, List[np.ndarray], np.ndarray]:
    """``(count, [means...], var)`` of one fused mean+variance answer as
    the client holds it: ``(mean, {"mean", "var", "count"})``."""
    mean, var = answer
    flat = lambda a: np.asarray(a, np.float64).reshape(-1)  # noqa: E731
    return (float(np.asarray(var["count"])),
            [flat(mean), flat(var["mean"])], flat(var["var"]))


def readings(answers: Sequence, count: np.ndarray, mean: np.ndarray,
             var: np.ndarray) -> Dict[str, float]:
    """The widest gaps between answers and the float64 reference:
    ``count_err`` (rows), ``mean_err`` and ``var_err`` (absolute, over
    every voxel of every compared answer; the volumes are unit-variance
    normals, so absolute is relative to their scale)."""
    out = {"count_err": 0.0, "mean_err": 0.0, "var_err": 0.0}
    for i, ans in enumerate(answers):
        if ans is None:
            return {k: float("inf") for k in out}
        n, means, v = answer_parts(ans)
        bad = any(not np.isfinite(m).all() for m in means) \
            or not np.isfinite(v).all()
        if bad or means[0].shape != mean[i].shape:
            return {k: float("inf") for k in out}
        out["count_err"] = max(out["count_err"], abs(n - float(count[i])))
        for m in means:
            out["mean_err"] = max(out["mean_err"],
                                  float(np.abs(m - mean[i]).max(initial=0)))
        out["var_err"] = max(out["var_err"],
                             float(np.abs(v - var[i]).max(initial=0)))
    return out


def control_readings(c_count: np.ndarray, c_mean: np.ndarray,
                     c_var: np.ndarray, count: np.ndarray, mean: np.ndarray,
                     var: np.ndarray) -> Dict[str, float]:
    """The control's gaps, in the form of :func:`readings`."""
    answers = [(c_mean[i], {"mean": c_mean[i], "var": c_var[i],
                            "count": c_count[i]})
               for i in range(len(c_count))]
    return readings(answers, count, mean, var)


def within(read: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(read[k] <= limits[k] for k in NUMBERS)
