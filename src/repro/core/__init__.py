"""ColoGrid core — the paper's contribution as a composable JAX library.

The subpackage mirrors HadoopBase-MIP's backend (Bao et al., 2017):

- :mod:`repro.core.table`       — HBase-analogue columnar ``TensorTable``.
- :mod:`repro.core.regions`     — region abstraction + split policies.
- :mod:`repro.core.balancer`    — data-allocation strategies (HBase-default
  balanced, the paper's greedy ``#CPU x MIPS`` balancer, SGE central store).
- :mod:`repro.core.placement`   — region->device placement realized as JAX
  sharded layouts + per-device task schedules.
- :mod:`repro.core.mapreduce`   — ``shard_map`` MapReduce engine over the mesh.
- :mod:`repro.core.chunk_model` — the paper's eq. (1)-(8) wall/resource-time
  model and the chunk-size (eta) optimizer.
- :mod:`repro.core.stats`       — summary-statistic MapReduce programs.
- :mod:`repro.core.query`       — index-family predicate pushdown vs naive scan.
- :mod:`repro.core.plan`        — :class:`GridQuery`, lazy scan→filter→map→
  reduce job plans with region pruning, projection pushdown, program fusion.
- :mod:`repro.core.simulator`   — discrete-event cluster simulator (Hadoop/SGE).
- :mod:`repro.core.scheduler`   — grid scheduler: rounds, stragglers, failures.
- :mod:`repro.core.blockstore`  — :class:`BlockStore`, content-addressed
  copy-on-write per-region device blocks shared across epochs and plans.
- :mod:`repro.core.grid`        — :class:`GridSession`, the five-verb facade
  (upload / retrieve / remove / rebalance / run) with mutation epochs,
  incremental placement, and a compiled-plan cache.
- :mod:`repro.core.frontend`    — :class:`GridFrontend`, concurrent query
  serving: single-flight coalescing, batched device ticks, epoch-isolated
  mutation, admission control.
- :mod:`repro.core.spans`       — per-query spans and timing records
  (``RunReport.trace``) on the profiler's clock.
"""

from repro.core.table import TensorTable, ColumnFamily, ColumnSpec
from repro.core.regions import (
    Region,
    RegionSet,
    ConstantSizeSplitPolicy,
    HierarchicalSplitPolicy,
)
from repro.core.balancer import (
    NodeSpec,
    assign_new_regions,
    balanced_allocation,
    greedy_allocation,
    central_allocation,
    rebalance,
    allocation_imbalance,
)
from repro.core.placement import Placement
from repro.core.chunk_model import (
    ChunkModelParams,
    ChunkModel,
    PAPER_PARAMS,
    TPU_V5E_PARAMS,
)
from repro.core.mapreduce import MapReduceEngine, MapReduceProgram
from repro.core.stats import (
    CountProgram,
    MeanProgram,
    VarianceProgram,
    MomentsProgram,
    HistogramProgram,
    FusedProgram,
    GroupedProgram,
    GroupedResult,
)
from repro.core.query import indexed_query, naive_query, QueryStats
from repro.core.plan import GridQuery, prefix_range
from repro.core.blockstore import BlockStore, DeviceBlock, LRUCache
from repro.core.grid import GridSession, RunReport, SessionMetrics
from repro.core.frontend import (
    FrontendOverloadedError,
    FrontendStats,
    GridFrontend,
    QueryTimeoutError,
)

__all__ = [
    "GridSession", "RunReport", "SessionMetrics",
    "GridFrontend", "FrontendStats",
    "FrontendOverloadedError", "QueryTimeoutError",
    "TensorTable", "ColumnFamily", "ColumnSpec",
    "Region", "RegionSet", "ConstantSizeSplitPolicy", "HierarchicalSplitPolicy",
    "NodeSpec", "assign_new_regions", "balanced_allocation",
    "greedy_allocation", "central_allocation",
    "rebalance", "allocation_imbalance",
    "Placement",
    "ChunkModelParams", "ChunkModel", "PAPER_PARAMS", "TPU_V5E_PARAMS",
    "MapReduceEngine", "MapReduceProgram",
    "CountProgram", "MeanProgram", "VarianceProgram", "MomentsProgram",
    "HistogramProgram", "FusedProgram", "GroupedProgram", "GroupedResult",
    "indexed_query", "naive_query", "QueryStats",
    "GridQuery", "prefix_range",
    "BlockStore", "DeviceBlock", "LRUCache",
]
