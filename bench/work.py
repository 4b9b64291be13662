"""Bytes a fold has to move, counted from shapes.

A block fold of a subset query has to read the payload of the rows the
query selected and write its accumulators once.  Pad rows, unselected
rows and whatever the kernel computes to combine them (the one-hot
contraction) are not counted, so no implementation can do the same work
in fewer bytes, and a share of the bandwidth roofline computed from these
bytes cannot pass 100%.  The statistic needs a few operations per
element, so bandwidth, not arithmetic, bounds it.
"""

from __future__ import annotations

from typing import Sequence

#: the shared accumulators each program needs (count, power sums)
ACCUMULATORS = {"mean": ("count", "s1"), "variance": ("count", "s1", "s2")}


def accumulator_names(programs: Sequence[str]) -> tuple:
    names = set()
    for p in programs:
        names.update(ACCUMULATORS[p])
    return tuple(n for n in ("count", "s1", "s2") if n in names)


def accumulator_bytes(features: int, programs: Sequence[str],
                      groups: int = 1) -> int:
    """float32 accumulators one fold writes: a count per group and a
    ``[features]`` sum per group for each power."""
    names = accumulator_names(programs)
    return sum(4 * groups if n == "count" else 4 * groups * features
               for n in names)


def fold_bytes(selected_rows: int, folds: int, features: int,
               itemsize: int, programs: Sequence[str],
               groups: int = 1) -> int:
    """Bytes ``folds`` block folds must move to fold ``selected_rows``
    selected rows in all."""
    return (selected_rows * features * itemsize
            + folds * accumulator_bytes(features, programs, groups))
