"""The cohort generator: a configuration's T1 cohort, made from ``--seed``.

The layout is one fixed multiset per configuration, drawn from its
``layout_seed``: the subjects' logical sizes (in key order, so the region
split is the same for every seed) and the (age, sex) pairs of the paper's
Table-3 strata at the configuration's scale.  ``--seed`` permutes the
(age, sex) pairs over the subjects and draws the volumes, so every seed
serves the same amount of work over different data.

Volumes are float32 standard normals plus age/100 per subject, as
``repro.data.pipeline.synthetic_image_population`` draws them.  They are
drawn in a fixed number of row chunks, each from its own child of the
seed's ``SeedSequence``, filled by a thread pool into one preallocated
array: the values depend on the seed only, not on the number of threads.
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, Optional, Sequence, Tuple

import numpy as np

#: row chunks the volumes are drawn in (fixed: it is part of the seed's
#: meaning); the pool's width only sets how many are drawn at once
CHUNKS = 64


@dataclasses.dataclass
class Cohort:
    """The benchmark's own copy of what it uploads."""

    keys: list
    ages: np.ndarray        # float32 [N]
    sexes: np.ndarray       # int8 [N]: 1 female, 0 male
    sizes: np.ndarray       # int64 [N] logical bytes (split column)
    data: np.ndarray        # float32 [N, *volume_shape]

    @property
    def num_rows(self) -> int:
        return len(self.keys)


def volume_shape(cfg: Dict) -> Tuple[int, ...]:
    return tuple(int(d) for d in cfg["volume_shape"])


def layout(cfg: Dict) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The configuration's fixed multiset: ``(ages, sexes, sizes)``,
    ages and sexes in stratum order, sizes in key order."""
    rng = np.random.default_rng(int(cfg["layout_seed"]))
    ages, sexes = [], []
    for lo, hi, females, males in cfg["strata"]:
        for sex, count in ((1, females), (0, males)):
            n = max(int(round(count * float(cfg["scale"]))), 1)
            ages.append(rng.uniform(lo, hi, n).astype(np.float32))
            sexes.append(np.full(n, sex, np.int8))
    ages = np.concatenate(ages)
    sexes = np.concatenate(sexes)
    lo, hi = cfg["size_bytes_range"]
    sizes = rng.integers(int(lo), int(hi) + 1, len(ages))
    return ages, sexes, sizes


def _draws(cfg: Dict, seed: int, shape: Optional[Sequence[int]]):
    """What ``seed`` fixes: the volume shape, the permuted ages and sexes,
    the sizes, the row chunks' bounds and each chunk's seed."""
    shape = tuple(shape) if shape is not None else volume_shape(cfg)
    ages, sexes, sizes = layout(cfg)
    n = len(ages)
    order_seq, data_seq = np.random.SeedSequence(int(seed)).spawn(2)
    order = np.random.default_rng(order_seq).permutation(n)
    bounds = np.linspace(0, n, min(CHUNKS, n) + 1).astype(int)
    return (shape, ages[order], sexes[order], sizes, bounds,
            data_seq.spawn(len(bounds) - 1))


def _fill(out: np.ndarray, child, ages: np.ndarray) -> None:
    """Draw one chunk's volumes into ``out`` (its rows' ``ages``)."""
    np.random.default_rng(child).standard_normal(dtype=np.float32, out=out)
    out += (ages / np.float32(100.0)).reshape((len(out),)
                                              + (1,) * (out.ndim - 1))


def _workers(threads: Optional[int]) -> int:
    return threads or min(16, os.cpu_count() or 1)


def build(cfg: Dict, seed: int, shape: Optional[Sequence[int]] = None,
          threads: Optional[int] = None) -> Cohort:
    """Draw the cohort for ``seed``; ``shape`` overrides the volume shape
    (the CPU tests' tiny volumes)."""
    shape, ages, sexes, sizes, bounds, children = _draws(cfg, seed, shape)
    n = len(ages)
    data = np.empty((n,) + shape, np.float32)

    def fill(i: int) -> None:
        lo, hi = bounds[i], bounds[i + 1]
        _fill(data[lo:hi], children[i], ages[lo:hi])

    with ThreadPoolExecutor(_workers(threads)) as pool:
        list(pool.map(fill, range(len(children))))
    keys = [f"sub{i:06d}" for i in range(n)]
    return Cohort(keys, ages, sexes, sizes, data)


def volume_blocks(cfg: Dict, seed: int,
                  shape: Optional[Sequence[int]] = None,
                  threads: Optional[int] = None
                  ) -> Iterator[Tuple[int, np.ndarray]]:
    """The cohort's volumes for ``seed`` as ``(first_row, rows)`` blocks in
    row order, the same values as :func:`build` draws, one pool's width of
    chunks at a time: the reference reads them without holding the whole
    cohort."""
    shape, ages, _, _, bounds, children = _draws(cfg, seed, shape)
    width = _workers(threads)
    with ThreadPoolExecutor(width) as pool:
        for first in range(0, len(children), width):
            chunks = range(first, min(first + width, len(children)))
            lo, hi = bounds[chunks[0]], bounds[chunks[-1] + 1]
            block = np.empty((hi - lo,) + shape, np.float32)

            def fill(i: int) -> None:
                a, b = bounds[i], bounds[i + 1]
                _fill(block[a - lo:b - lo], children[i], ages[a:b])

            list(pool.map(fill, chunks))
            yield int(lo), block


def table_of(cohort: Cohort, cfg: Dict):
    """Upload the cohort into the system's ``TensorTable`` (one batch)."""
    from repro.core.regions import HierarchicalSplitPolicy
    from repro.core.table import ColumnFamily, ColumnSpec, TensorTable

    shape = cohort.data.shape[1:]
    table = TensorTable(
        "t1_population",
        [ColumnFamily("img", (ColumnSpec("data", shape, np.float32),)),
         ColumnFamily("idx", (ColumnSpec("size", (), np.int64),
                              ColumnSpec("age", (), np.float32),
                              ColumnSpec("sex", (), np.int8)))],
        split_policy=HierarchicalSplitPolicy(
            max_region_bytes=int(cfg["region_bytes"])))
    table.upload(cohort.keys,
                 {"img": {"data": cohort.data},
                  "idx": {"size": cohort.sizes, "age": cohort.ages,
                          "sex": cohort.sexes}})
    return table
