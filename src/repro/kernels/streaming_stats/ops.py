"""Public op: masked streaming stats over a chunk of rows.

Handles arbitrary row shapes (flattens features), dispatches to the fused
fold Pallas kernel (or the jnp reference when ``impl='ref'``), and exposes
a MapReduce program so the engine's map phase can run on the kernel.

Since the fused fold kernel landed (``repro.kernels.fused_fold``), the
pallas path here is a facade: ``streaming_stats`` is exactly the
``(count, s1, s2)`` subset of the fused kernel's grouped accumulator pool
at ``G=1``.  The dedicated streaming-stats kernel is gone — one tiling,
one accumulation discipline, one equivalence suite for every power sum.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.mapreduce import MapReduceProgram
from repro.kernels.fused_fold.ops import fused_fold
from repro.kernels.streaming_stats.ref import streaming_stats_ref


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def streaming_stats(
    rows: jax.Array,       # [R, *feature_shape]
    mask: jax.Array,       # [R]
    impl: str = "pallas",
    interpret: bool = False,  # True only for CPU runs and tests
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (sum, sumsq, count); sum/sumsq have the row's feature shape."""
    if impl == "ref":
        R = rows.shape[0]
        fshape = rows.shape[1:]
        s, sq, c = streaming_stats_ref(rows.reshape(R, -1), mask)
        return s.reshape(fshape), sq.reshape(fshape), c
    acc = fused_fold(rows, mask, names=("count", "s1", "s2"),
                     interpret=interpret)
    return acc["s1"][0], acc["s2"][0], acc["count"][0]


@dataclasses.dataclass(frozen=True)
class KernelMeanProgram(MapReduceProgram):
    """MeanProgram with the Pallas kernel as the map-phase fold."""

    interpret: bool = False
    additive = True

    def zero(self, row_shape, dtype):
        return {"sum": jnp.zeros(row_shape, jnp.float32),
                "count": jnp.zeros((), jnp.float32)}

    def map_chunk(self, rows, valid):
        s, _, c = streaming_stats(rows, valid, interpret=self.interpret)
        return {"sum": s, "count": c}

    def merge(self, a, b):
        return jax.tree.map(jnp.add, a, b)

    def finalize(self, p):
        return p["sum"] / jnp.maximum(p["count"], 1)


@dataclasses.dataclass(frozen=True)
class KernelSecondMomentProgram(MapReduceProgram):
    """Mean/variance/count from the kernel's ``(Σx, Σx², n)`` — the
    Pallas-backed analogue of ``VarianceProgram``'s finalize contract
    (raw-sums form instead of the Chan merge; equal up to float
    associativity, and additive so the reduce stays one ``psum``)."""

    interpret: bool = False
    additive = True

    def zero(self, row_shape, dtype):
        z = jnp.zeros(row_shape, jnp.float32)
        return {"s1": z, "s2": z, "count": jnp.zeros((), jnp.float32)}

    def map_chunk(self, rows, valid):
        s, sq, c = streaming_stats(rows, valid, interpret=self.interpret)
        return {"s1": s, "s2": sq, "count": c}

    def merge(self, a, b):
        return jax.tree.map(jnp.add, a, b)

    def finalize(self, p):
        n = jnp.maximum(p["count"], 1)
        mean = p["s1"] / n
        var = jnp.maximum(p["s2"] / n - mean * mean, 0)
        return {"mean": mean, "var": var, "count": p["count"]}


def kernel_map_program(program: MapReduceProgram, impl: str = "pallas",
                       interpret: bool = False) -> MapReduceProgram:
    """The Pallas map-phase twin of a sum/count-family program.

    ``GridSession.run(..., impl="pallas")`` routes through here: the
    returned program folds each chunk with :func:`streaming_stats` (one
    HBM→VMEM streaming pass producing Σx/Σx²/count on the fused fold
    kernel) and finalizes to the same result contract as the jnp
    reference program.  Kernel programs accumulate fp32 (the kernel's
    VMEM accumulator dtype).  Programs whose statistic is not a
    projection of (Σx, Σx², n) have no kernel twin — ask for them with
    the default reference impl.
    """
    from repro.core.stats import MeanProgram, VarianceProgram

    if impl != "pallas":
        raise ValueError(f"unknown map-phase impl {impl!r}; "
                         "use impl='pallas' or the default reference path")
    if isinstance(program, MeanProgram):
        return KernelMeanProgram(interpret=interpret)
    if isinstance(program, VarianceProgram):
        return KernelSecondMomentProgram(interpret=interpret)
    raise ValueError(
        f"no pallas map phase for {type(program).__name__}: the "
        "streaming_stats kernel covers the sum/count family "
        "(MeanProgram, VarianceProgram)")
