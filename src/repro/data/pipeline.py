"""Data pipeline — training batches served from the colocation grid.

The same ``TensorTable``/``Placement``/balancer machinery that serves the
paper's imaging workload doubles as the LM training data layer: token
sequences are rows (one row = one fixed-length sample), regions are the unit
of placement, and each data-parallel device group draws its per-step
microbatch from *its own* shard — the colocation guarantee means a training
step's input pipeline does zero cross-device traffic, and re-balancing (e.g.
after elastic rescale) is a region move, not a dataset reshuffle.

Synthetic generators provide the two dataset families the repo needs:
token corpora (LM workloads) and the paper's 5,153-image T1 population with
the Table-3 age/sex strata.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.balancer import NodeSpec
from repro.core.placement import Placement
from repro.core.regions import HierarchicalSplitPolicy
from repro.core.table import ColumnFamily, ColumnSpec, TensorTable


# ----------------------------------------------------------------------
# synthetic datasets
# ----------------------------------------------------------------------

def synthetic_token_table(
    n_rows: int,
    seq_len: int,
    vocab: int,
    seed: int = 0,
    region_bytes: int = 1 << 22,
) -> TensorTable:
    """A token corpus as a TensorTable: ``tok:ids`` + ``idx:size``."""
    rng = np.random.default_rng(seed)
    table = TensorTable(
        "tokens",
        [
            ColumnFamily("tok", (ColumnSpec("ids", (seq_len,), np.int32),)),
            ColumnFamily("idx", (ColumnSpec("size", (), np.int64),)),
        ],
        split_policy=HierarchicalSplitPolicy(max_region_bytes=region_bytes),
    )
    # mixture of zipf-ish unigram draws — enough structure for loss to move
    probs = 1.0 / np.arange(1, vocab + 1) ** 1.1
    probs /= probs.sum()
    ids = rng.choice(vocab, size=(n_rows, seq_len), p=probs).astype(np.int32)
    sizes = np.full(n_rows, seq_len * 4, np.int64)
    table.upload(
        [f"doc{i:08d}" for i in range(n_rows)],
        {"tok": {"ids": ids}, "idx": {"size": sizes}},
    )
    return table


#: Table 3 of the paper: (age_lo, age_hi, female_count, male_count)
PAPER_STRATA = (
    (4.0, 20.0, 1157, 698),
    (20.0, 40.0, 651, 648),
    (40.0, 60.0, 230, 280),
    (60.0, 98.0, 332, 494),
)


def synthetic_image_population(
    payload_shape: Tuple[int, ...] = (16, 16, 16),
    scale: float = 1.0,
    seed: int = 0,
    region_bytes: int = 1 << 31,
) -> TensorTable:
    """The paper's study population per Table 3 strata (4,490 subjects;
    the paper's 5,153 figure counts *images* — some subjects have repeat
    scans), with logical sizes drawn from [SizeSmall, SizeBig] = [6, 20] MB.
    ``scale`` < 1 shrinks each stratum proportionally for CI-speed runs.

    ``region_bytes`` is the hierarchical split threshold over those logical
    sizes, so it sets how many subjects a region (and so a device block)
    holds.  The sizes are drawn before the payload, so the region layout
    depends on ``(scale, seed, region_bytes)`` and not on the volume shape.
    Volumes are drawn as float32 normals straight into the table's payload
    array: a full-size cohort never has a float64 copy on the host."""
    rng = np.random.default_rng(seed)
    rows = []
    for lo, hi, f_cnt, m_cnt in PAPER_STRATA:
        for sex, cnt in ((1, f_cnt), (0, m_cnt)):
            n = max(int(round(cnt * scale)), 1)
            ages = rng.uniform(lo, hi, n).astype(np.float32)
            rows.extend((a, sex) for a in ages)
    n = len(rows)
    ages = np.array([r[0] for r in rows], np.float32)
    sexes = np.array([r[1] for r in rows], np.int8)
    order = rng.permutation(n)
    ages, sexes = ages[order], sexes[order]
    sizes = rng.integers(6_000_000, 20_000_001, n)

    table = TensorTable(
        "t1_population",
        [
            ColumnFamily("img", (ColumnSpec("data", payload_shape, np.float32),)),
            ColumnFamily("idx", (
                ColumnSpec("size", (), np.int64),
                ColumnSpec("age", (), np.float32),
                ColumnSpec("sex", (), np.int8),
            )),
        ],
        split_policy=HierarchicalSplitPolicy(max_region_bytes=region_bytes),
    )
    data = np.empty((n,) + tuple(payload_shape), np.float32)
    rng.standard_normal(dtype=np.float32, out=data)
    # age covariate leaks into the volumes so subset averages differ measurably
    data += (ages / np.float32(100.0)).reshape((n,) + (1,) * len(payload_shape))
    table.upload(
        [f"sub{i:06d}" for i in range(n)],
        {"img": {"data": data},
         "idx": {"size": sizes, "age": ages, "sex": sexes}},
    )
    return table


# ----------------------------------------------------------------------
# colocated loader
# ----------------------------------------------------------------------

class ColocatedTokenDataset:
    """Serves ``[global_batch, seq]`` batches, each device group reading only
    its local shard (device-local gather indices, no cross-shard traffic)."""

    def __init__(
        self,
        table: TensorTable,
        mesh: Mesh,
        global_batch: int,
        data_axis: str = "data",
        batch_axes: Sequence[str] = ("data",),
        strategy: str = "greedy",
        nodes: Optional[Sequence[NodeSpec]] = None,
        seed: int = 0,
        placement: Optional[Placement] = None,
    ):
        self.table = table
        self.mesh = mesh
        self.global_batch = global_batch
        self.data_axis = data_axis
        self.batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
        D = int(np.prod([mesh.shape[a] for a in self.batch_axes]))
        if global_batch % D != 0:
            raise ValueError(f"global_batch {global_batch} % {D} != 0")
        self.per_shard = global_batch // D
        self.D = D
        if placement is not None:
            # ride an existing region→device map (e.g. a GridSession's)
            if len(placement.nodes) != D:
                raise ValueError(
                    f"placement has {len(placement.nodes)} nodes, need {D}")
            self.placement = placement
        else:
            if nodes is None:
                nodes = [NodeSpec(i, cores=1, mips=1.0) for i in range(D)]
            self.placement = Placement.from_strategy(table, nodes, strategy)
        self._rng = np.random.default_rng(seed)
        self._pools_version = None
        self._compute_pools()
        self.seq_len = table.column_spec("tok", "ids").shape[0]

    def _compute_pools(self) -> None:
        """Per-shard row pools (positions into the table's row order).

        Cached by the (table mutations, placement version) pair: under a
        shared (GridSession) placement the table mutates between steps and
        positional indices shift; for an immutable table this is free.
        """
        version = (self.table.mutation_count, self.placement.version)
        if version == self._pools_version:
            return
        self._pools = [self.placement.rows_for_node(n.node_id)
                       for n in self.placement.nodes]
        for i, pool in enumerate(self._pools):
            if len(pool) == 0:
                raise ValueError(f"node {i} received no rows; "
                                 "table too small for this mesh")
        self._pools_version = version

    def batch_sharding(self) -> NamedSharding:
        axes = self.batch_axes
        spec = axes[0] if len(axes) == 1 else tuple(axes)
        return NamedSharding(self.mesh, P(spec))

    def next_batch(self, step: int) -> jax.Array:
        """Deterministic per-step batch: shard d draws from pool d."""
        self._compute_pools()
        ids = np.empty((self.D, self.per_shard, self.seq_len), np.int32)
        col = self.table.column("tok", "ids")
        for d, pool in enumerate(self._pools):
            rng = np.random.default_rng((hash(("batch", step, d)) & 0x7FFFFFFF))
            take = rng.choice(pool, size=self.per_shard, replace=True)
            ids[d] = col[take]
        flat = ids.reshape(self.global_batch, self.seq_len)
        return jax.device_put(flat, self.batch_sharding())

    def __iter__(self) -> Iterator[jax.Array]:
        step = 0
        while True:
            yield self.next_batch(step)
            step += 1
