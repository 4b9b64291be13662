"""MapReduce engine over the device mesh — the paper's §2.2 on TPU.

The paper's Map phase sends each chunk of η images to the node holding them;
the Reduce phase combines the per-chunk intermediates on one node.  On a TPU
mesh this becomes:

- **Map**: a ``shard_map`` body on the ``data`` axis.  Each device scans its
  *local* table shard (placed by :mod:`repro.core.placement`, so no input
  bytes cross the interconnect) in chunks of η rows, folding each chunk into a
  running partial with the program's ``map_chunk``/``merge``.  Devices with
  fewer real rows run the same number of lockstep rounds with masked-out
  chunks — the SPMD analogue of idle cores waiting on the longest map task
  (eq. 2's worst-case term).
- **Shuffle/Reduce**: only the tiny partials move.  Additive programs reduce
  with a single ``psum`` (an all-reduce the ICI does in hardware); general
  associative merges use an ``all_gather`` of partials followed by a fold.
  Either way the network carries ``O(#job · |partial|)`` bytes — the colocation
  win over SGE, which must move ``O(#img · SizeBig)``.

Programs are associative-merge folds (monoids), which is exactly the structure
the paper's ANTS AverageImages use case has, and what makes chunk size η a
free *performance* parameter with no effect on the result (a property test
asserts chunk-size invariance up to float associativity).

Two execution granularities share the program interface:

- :meth:`MapReduceEngine.run` — the layout-at-a-time path: one ``shard_map``
  fold over an assembled ``[D, C, ...]`` array (used by standalone layouts
  and the compact one-shot gather path);
- :meth:`MapReduceEngine.fold_block` + :meth:`MapReduceEngine.merge_finalize`
  — the block-at-a-time path :class:`~repro.core.grid.GridSession` drives:
  each region's device block folds independently on its owner device (the
  jitted fold runs where the committed block lives — the map phase), then
  the tiny partials reduce.  Additive programs on a 1-D data mesh
  **tree-reduce**: each owner pre-merges its own partials locally and one
  ``psum`` over the data axis joins them (the ICI's hardware all-reduce);
  everything else funnels to one device for a single jitted merge+finalize.
  Because partials are per-block, they are cacheable per block lineage in
  the :class:`~repro.core.blockstore.BlockStore` — a repeat query merges
  cached partials and folds zero payload rows.  Fold executables are keyed
  by block rows padded to the next power of two and funnel merges by the
  pow2-bucketed partial count, so drifting region sizes and block counts
  share a handful of compiles.  Grouped folds (``gids``/``num_groups``,
  see :class:`~repro.core.stats.GroupedProgram`) produce group-keyed
  partials in the same single pass.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import spans
from repro.core.blockstore import LRUCache
from repro.utils import shard_map_compat

PyTree = Any


def partial_to_host(partial: PyTree) -> Tuple[List[np.ndarray], Any]:
    """Flatten a fold partial into host numpy leaves + its treedef — the
    serialization half of the BlockStore's partial spill tier.  Device
    leaves are pulled to host; the treedef round-trips the pytree shape
    through :func:`partial_from_host` without pickling the structure."""
    leaves, treedef = jax.tree_util.tree_flatten(partial)
    return [np.asarray(leaf) for leaf in leaves], treedef


def partial_from_host(leaves: Sequence[np.ndarray], treedef: Any) -> PyTree:
    """Rebuild a spilled fold partial from its host leaves.  Leaves stay
    numpy — the merge paths accept host arrays and JAX converts on first
    use, so promotion costs no eager ``device_put``."""
    return jax.tree_util.tree_unflatten(treedef, list(leaves))


class MapReduceProgram:
    """An associative summary-statistic program (a commutative monoid).

    Subclasses define:
      * ``zero(row_shape, dtype)``  — identity partial;
      * ``map_chunk(rows, valid)``  — fold a ``[eta, ...]`` chunk (with a
        ``[eta]`` validity mask) into a partial;
      * ``merge(a, b)``             — associative combine of partials;
      * ``finalize(partial)``       — partial -> user-facing result.

    ``additive`` marks programs whose partials combine by a per-leaf
    elementwise operator — sum by default, or the operator named by
    :meth:`merge_ops_for` — enabling the single-collective reduce path
    (``psum``/``pmax``).

    Programs whose statistic is a projection of the raw power sums may also
    declare :meth:`requires` / :meth:`finalize_shared`; a CSE'd
    :class:`~repro.core.stats.FusedProgram` then computes each shared
    accumulator once per chunk and projects per-member results, instead of
    re-folding the chunk once per member.
    """

    additive: bool = False

    def cache_key(self) -> Tuple[str, str]:
        """Stable identity for executable/plan caches.

        Default: type name + repr — correct for the frozen-dataclass
        programs in :mod:`repro.core.stats` (repr encodes every parameter).
        Programs with unhashable/unstable reprs should override.
        """
        return (type(self).__name__, repr(self))

    def zero(self, row_shape: Tuple[int, ...], dtype) -> PyTree:
        raise NotImplementedError

    def map_chunk(self, rows: jax.Array, valid: jax.Array) -> PyTree:
        raise NotImplementedError

    def merge(self, a: PyTree, b: PyTree) -> PyTree:
        raise NotImplementedError

    def merge_ops_for(self, partial: PyTree) -> Optional[List[str]]:
        """Per-leaf merge operators for an ``additive`` program, aligned
        with ``jax.tree.leaves(partial)``: each entry is ``"sum"`` or
        ``"max"``.  ``None`` (the default) means every leaf merges by
        elementwise sum — the classic additive monoid.

        This is how a max-merge sketch (HyperLogLog registers) rides the
        engine's additive fast paths: the tree reduce issues ``pmax``
        instead of ``psum`` for ``"max"`` leaves, and the stacked funnel /
        owner pre-merge reduce with ``max(axis=0)`` instead of
        ``sum(axis=0)``.  Contract: ``zero()`` must be the identity of
        each leaf's operator (0 works for both sum and max over
        non-negative registers), and ``merge`` must agree leafwise with
        the declared operators.  Only consulted when ``additive``; the
        argument may be a tracer — implementations may inspect only its
        tree structure, never its values."""
        return None

    def finalize(self, partial: PyTree) -> PyTree:
        raise NotImplementedError

    # --- common-subexpression sharing protocol (optional) -------------

    def requires(self) -> Tuple[str, ...]:
        """Raw shared accumulators this program's result projects from
        (a subset of ``repro.core.stats.SHARED_ACCUMULATORS``: ``count``,
        ``s1`` .. ``s4``).  Empty (the default) means the program folds its
        own private accumulator even inside a CSE'd fusion."""
        return ()

    def finalize_shared(self, shared: Mapping[str, jax.Array]) -> PyTree:
        """Project the user-facing result from the shared accumulators
        named by :meth:`requires`.  Must agree with
        ``finalize(own fold)`` up to float associativity."""
        raise NotImplementedError

    # --- fused-kernel fold protocol (optional) ------------------------

    def shared_fold_spec(self) -> Optional[Tuple[str, ...]]:
        """The shared-accumulator names whose fp32 pool fully determines
        this program's partial, or ``None`` if the partial needs anything
        outside the pool (private accumulators, non-fp32 pools).  Non-None
        makes the program eligible for the engine's fused Pallas fold
        (``fold_impl="pallas"``): the kernel emits the pool in one HBM pass
        and :meth:`partial_from_shared` shapes it into the program's
        native partial — bitwise-compatible with the XLA fold up to fp32
        accumulation order."""
        return None

    def partial_from_shared(self, shared: Mapping[str, jax.Array]) -> PyTree:
        """Build this program's partial from the kernel-folded shared
        pool (``{name: acc}``; grouped folds carry a leading group axis on
        every leaf).  Must merge/finalize identically to a partial the
        program folded itself, up to float associativity."""
        raise NotImplementedError


def _checked_merge_ops(program: MapReduceProgram,
                       partial: PyTree) -> Optional[List[str]]:
    """The program's per-leaf merge operators, validated against the
    partial's actual leaf count — ``None`` for the all-sum common case."""
    ops = program.merge_ops_for(partial)
    if ops is None:
        return None
    n_leaves = len(jax.tree_util.tree_leaves(partial))
    if len(ops) != n_leaves:
        raise ValueError(
            f"{type(program).__name__}.merge_ops_for returned {len(ops)} "
            f"operators for a partial with {n_leaves} leaves")
    bad = sorted(set(ops) - {"sum", "max"})
    if bad:
        raise ValueError(f"unknown merge operators {bad}; "
                         "expected 'sum' or 'max'")
    return ops


def _off_device_bytes(partial: PyTree, dev) -> int:
    """Bytes of ``partial``'s device leaves held by a chip other than
    ``dev`` (host leaves cross from the host, not from a chip)."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(partial)
               if isinstance(x, jax.Array) and dev not in x.devices())


def _combine_leafwise(partial_like: PyTree, ops: Optional[List[str]],
                      sum_fn: Callable[[Any], Any],
                      max_fn: Callable[[Any], Any]) -> PyTree:
    """Apply ``sum_fn`` / ``max_fn`` leaf-by-leaf per the operator list
    (``None`` = all sum) and rebuild the tree."""
    if ops is None:
        return jax.tree.map(sum_fn, partial_like)
    leaves, treedef = jax.tree_util.tree_flatten(partial_like)
    out = [max_fn(leaf) if op == "max" else sum_fn(leaf)
           for leaf, op in zip(leaves, ops)]
    return jax.tree_util.tree_unflatten(treedef, out)


@dataclasses.dataclass
class MergeRecord:
    """What one :meth:`MapReduceEngine.merge_finalize` did, filled in for
    the caller that passed it: the physical reduce it took (``"tree"`` /
    ``"funnel"``) and the partial bytes ``device_put`` moved from one chip
    to another before the reduce (the all-reduce itself not counted)."""

    path: str = ""
    cross_chip_bytes: int = 0


@dataclasses.dataclass
class MapReduceStats:
    """Byte accounting for one run (feeds EXPERIMENTS.md and the simulator
    cross-check)."""

    local_rows_read: int          # rows folded on their home device
    local_bytes_read: int         # physical payload bytes read from HBM
    shuffle_bytes: int            # partial bytes crossing the interconnect
    rounds: int                   # lockstep map rounds (wall-clock proxy)
    chunks: int                   # Σ real chunks (#job; resource proxy)
    chunk_size: int
    # block folds run on the fused kernel, by its schedule (see
    # MapReduceEngine.kernel_schedule): the ungrouped VPU row sum and the
    # grouped one-hot MXU contraction
    kernel_folds_rowsum: int = 0
    kernel_folds_onehot: int = 0


class MapReduceEngine:
    """Executes MapReduce programs over ``[D, C, ...]`` colocated layouts."""

    def __init__(self, mesh: Mesh, data_axis: str = "data",
                 executable_cache_cap: int = 64,
                 block_pad: str = "pow2",
                 merge_strategy: str = "auto",
                 fold_impl: str = "pallas",
                 fold_interpret: bool = False,
                 fault_injector=None):
        self.mesh = mesh
        self.data_axis = data_axis
        #: optional chaos harness (repro.core.faults.FaultInjector): every
        #: block-fold dispatch fires its "fold" site with the owner device,
        #: so injected fold faults (transient, permanent owner loss,
        #: straggler delay) surface here and the session's retry/quarantine
        #: wrapper around fold_block owns the response
        self.fault_injector = fault_injector
        # LRU-capped: one entry per (program, row signature, eta, C); an
        # evicted executable rebuilds on next use (compile_count bumps again)
        self._compiled = LRUCache(executable_cache_cap)
        # partial byte sizes per (program, row signature): plain dict — tiny
        # ints, not executables, so no cap and no compile_count coupling
        self._partial_bytes: dict = {}
        # builds of new executables (the recompile oracle GridSession's plan
        # cache is tested against): bumped only on an executable-cache miss.
        self.compile_count = 0
        #: per-block fold executables are shape-keyed; "pow2" pads block rows
        #: up to the next power of two before the jitted fold, so the key
        #: space stays O(log max_rows) however many distinct region sizes a
        #: (grouped) workload produces.  "none" keys on exact row counts.
        if block_pad not in ("pow2", "none"):
            raise ValueError(f"unknown block_pad policy {block_pad!r}")
        self.block_pad = block_pad
        #: "auto" tree-reduces additive merges across owner devices when the
        #: mesh allows it; "funnel" forces the single-device reduce (the
        #: comparison baseline the merge bench uses).
        if merge_strategy not in ("auto", "funnel"):
            raise ValueError(f"unknown merge_strategy {merge_strategy!r}")
        self.merge_strategy = merge_strategy
        #: "pallas" streams each CSE-eligible block fold through the fused
        #: Pallas kernel (one HBM pass emits the whole grouped accumulator
        #: pool); "xla" forces the reference scan-of-chunks fold.  The
        #: pallas setting falls back per fold signature — see
        #: :meth:`fold_path` — so it is always safe to leave on.
        if fold_impl not in ("pallas", "xla"):
            raise ValueError(f"unknown fold_impl {fold_impl!r}")
        self.fold_impl = fold_impl
        #: run the Pallas kernel in interpret mode off-TPU (tests/benches on
        #: the CPU container).  Off by default: without it, non-TPU
        #: platforms take the XLA fold — interpret mode is a correctness
        #: harness, not a fast path.
        self.fold_interpret = bool(fold_interpret)
        #: what the kernels are given: interpret mode only off the chip,
        #: so a TPU always compiles them whatever ``fold_interpret`` says
        self.kernel_interpret = (self.fold_interpret
                                 and jax.default_backend() != "tpu")
        #: folds dispatched per implementation (observability + tests);
        #: bumped under ``_count_lock`` — concurrent frontend queries fold
        #: from many threads and the barrier tests assert EXACT counts
        self.fold_path_counts: dict = {"pallas": 0, "xla": 0}
        self.merge_path_counts: dict = {"tree": 0, "funnel": 0}
        # executable builds are serialized (two threads missing the same
        # key must not compile twice and double-bump compile_count); the
        # dispatch of an already-built executable stays lock-free
        self._build_lock = threading.RLock()
        self._count_lock = threading.Lock()
        # launches of programs that span the mesh (a psum) are serialized:
        # two queries' collectives enqueued in different orders on two
        # chips would each wait for the other on the interconnect
        self._collective_lock = threading.Lock()
        # per-device identity partials padding the tree merge's presums
        self._identities = LRUCache(16)
        # the mesh's data-axis devices, in shard order — available only when
        # the mesh is exactly the 1-D data axis (same condition the session
        # uses for per-shard block placement); None disables the tree reduce
        devs = np.asarray(mesh.devices).flat
        self._axis_devices = (list(devs)
                              if mesh.axis_names == (data_axis,) else None)

    # ------------------------------------------------------------------

    def _build(self, program: MapReduceProgram, row_shape, dtype, eta: int):
        """Build the jitted shard_map fold for a given row signature."""
        data_axis = self.data_axis
        mesh = self.mesh
        rep_axes = tuple(a for a in mesh.axis_names if a != data_axis)

        def local_fold(values: jax.Array, valid: jax.Array) -> PyTree:
            # values: [1, C, ...] local shard; valid: [1, C]
            v = values[0]
            m = valid[0]
            C = v.shape[0]
            n_chunks = C // eta
            v = v.reshape((n_chunks, eta) + v.shape[1:])
            m = m.reshape((n_chunks, eta))

            def body(carry, xs):
                chunk, mask = xs
                return program.merge(carry, program.map_chunk(chunk, mask)), None

            init = program.zero(row_shape, dtype)
            partial, _ = jax.lax.scan(body, init, (v, m))
            return partial

        if program.additive:
            def mapper(values, valid):
                partial = local_fold(values, valid)
                # per-leaf collective: psum for sum leaves, pmax for max
                # leaves (HLL registers) — one hardware all-reduce either way
                ops = _checked_merge_ops(program, partial)
                return _combine_leafwise(
                    partial, ops,
                    lambda x: jax.lax.psum(x, axis_name=data_axis),
                    lambda x: jax.lax.pmax(x, axis_name=data_axis))
        else:
            def mapper(values, valid):
                partial = local_fold(values, valid)
                gathered = jax.tree.map(
                    lambda x: jax.lax.all_gather(x, axis_name=data_axis), partial
                )
                D = mesh.shape[data_axis]

                def fold(i, acc):
                    piece = jax.tree.map(lambda g: g[i], gathered)
                    return program.merge(acc, piece)

                first = jax.tree.map(lambda g: g[0], gathered)
                return jax.lax.fori_loop(1, D, fold, first)

        in_specs = (P(data_axis), P(data_axis))
        out_specs = jax.tree.map(lambda _: P(), program.zero(row_shape, dtype))

        fn = shard_map_compat(
            mapper, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check=False,
        )

        def run(values, valid):
            partial = fn(values, valid)
            return program.finalize(partial)

        return jax.jit(run)

    # ------------------------------------------------------------------
    # block-at-a-time path: per-block folds + one merge/finalize reduce
    # ------------------------------------------------------------------

    @property
    def _merge_device(self):
        """Where partials meet for the reduce phase (the paper's "combine on
        one node"): the mesh's first device.  Only ``O(#blocks · |partial|)``
        bytes ever travel here."""
        return list(np.asarray(self.mesh.devices).flat)[0]

    def _get_or_build(self, key, build: Callable[[], Any]):
        fn = self._compiled.get(key)
        if fn is None:
            with self._build_lock:
                # double-check under the lock: a racing thread may have
                # built it while we waited — compile once, count once
                fn = self._compiled.get(key)
                if fn is None:
                    self.compile_count += 1
                    fn = build()
                    self._compiled.put(key, fn)
        return fn

    @staticmethod
    def _next_pow2(n: int) -> int:
        return 1 << max(0, int(n) - 1).bit_length()

    def bucket_rows(self, rows: int) -> int:
        """The padded row count a block folds at: the next power of two
        under the "pow2" policy (bounding the executable key space to
        O(log max_rows) however many distinct region sizes exist), the
        exact count under "none".  ``GridSession`` commits device blocks
        pre-padded to this bucket, so the per-fold hot path never pays a
        pad copy — only freshly-shaped raw arrays do."""
        if self.block_pad == "pow2":
            return self._next_pow2(rows)
        return rows

    def fold_path(self, program: MapReduceProgram, dtype,
                  num_groups: int = 0) -> str:
        """Which implementation :meth:`fold_block` takes for this fold
        signature: ``"pallas"`` (the fused one-HBM-pass kernel) or
        ``"xla"`` (the reference scan of chunks).  Deterministic per
        (engine config, program, dtype, G), so the session can key cached
        partials on it.  Falls back to XLA when:

        - the program needs accumulators outside the fp32 CSE pool
          (``shared_fold_spec() is None`` — private members, int32 count,
          histograms, non-fp32 pools);
        - the platform lacks Pallas support and interpret mode was not
          requested (``fold_interpret`` covers CPU tests);
        - the payload dtype is not real-valued;
        - G exceeds the VMEM-budget threshold from the chunk model
          (``fused_fold.ops.max_groups_for_vmem``).
        """
        if self.fold_impl != "pallas":
            return "xla"
        if not (self.fold_interpret or jax.default_backend() == "tpu"):
            return "xla"
        names = program.shared_fold_spec()
        if not names:
            return "xla"
        dt = jnp.dtype(dtype)
        if not (jnp.issubdtype(dt, jnp.floating)
                or jnp.issubdtype(dt, jnp.integer)
                or dt == jnp.dtype(bool)):
            return "xla"
        from repro.kernels.fused_fold.ops import max_groups_for_vmem
        if max(1, int(num_groups)) > max_groups_for_vmem(names=names):
            return "xla"
        return "pallas"

    def kernel_schedule(self, program: MapReduceProgram, dtype,
                        num_groups: int = 0) -> str:
        """The fused kernel's schedule for this fold signature:
        ``"rowsum"`` (one group) or ``"onehot"`` (grouped); ``""`` when
        :meth:`fold_path` sends it to the XLA fold."""
        if self.fold_path(program, dtype, num_groups) != "pallas":
            return ""
        from repro.kernels.fused_fold.ops import fold_schedule
        return fold_schedule(num_groups)

    def _pallas_fold_fn(self, program: MapReduceProgram, rows: int,
                        row_shape, dtype, masked: bool, groups: int = 0):
        """The jitted fused-kernel fold for one block signature.  One
        streaming pass emits the whole shared pool; ``eta`` does not enter
        the executable key — the kernel is chunk-free, so every chunk size
        shares one compile per (bucketed rows, G).  Tile sizes divide the
        pow2 row bucket (both are powers of two), so executables stay
        keyed on ``bucket_rows`` exactly like the XLA path."""
        from repro.kernels.fused_fold.ops import fused_fold

        names = program.shared_fold_spec()
        grouped = groups > 0
        G = max(1, groups)
        # fold_path admits non-TPU backends only under fold_interpret
        interpret = self.kernel_interpret
        shape = tuple(row_shape)

        def fold(block, mask, gids):
            shared = fused_fold(
                block, mask, gids, num_groups=G, names=names,
                interpret=interpret)
            # a device block is committed as a [rows, features] slab (see
            # GridSession._put_block): restore the row shape on the pool
            shared = {n: a if n == "count" else a.reshape(a.shape[:1] + shape)
                      for n, a in shared.items()}
            if not grouped:   # ungrouped folds are the G=1 degenerate case
                shared = {n: a[0] for n, a in shared.items()}
            return program.partial_from_shared(shared)

        if grouped:
            if masked:
                return jax.jit(fold)
            return jax.jit(lambda block, gids: fold(block, None, gids))
        if masked:
            return jax.jit(lambda block, mask: fold(block, mask, None))
        return jax.jit(lambda block: fold(block, None, None))

    def _block_fold_fn(self, program: MapReduceProgram, rows: int,
                       row_shape, dtype, eta: int, masked: bool,
                       groups: int = 0):
        """The jitted fold for one block signature ``(rows, row_shape,
        dtype, η[, groups])``.  Padding to a chunk multiple happens inside
        the jit, so a committed device block folds on its own device with no
        host trip.  Executables are shape-keyed: blocks of equal (bucketed)
        row count share one compile.

        With ``groups > 0`` the program is a
        :class:`~repro.core.stats.GroupedProgram`: the fold additionally
        takes ``[rows]`` int32 group ids, and each chunk's ``[G, eta]``
        group mask (disjoint segment membership × validity) feeds the
        grouped ``map_chunk`` — one pass produces G partials.
        """
        pad = -rows % eta
        n_chunks = (rows + pad) // eta
        shape = tuple(row_shape)

        def fold(block, mask, gids):
            m = (jnp.ones((rows,), bool) if mask is None
                 else mask.astype(bool))
            v = block
            if pad:
                v = jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
                m = jnp.pad(m, [(0, pad)])
                if groups:
                    gids = jnp.pad(gids, [(0, pad)])
            # split rows only: a block committed as a [rows, features] slab
            # (GridSession._put_block) takes its row shape back one chunk
            # at a time, so on the chip's tiled layout the relayout copy is
            # one chunk, never the whole block
            v = v.reshape((n_chunks, eta) + v.shape[1:])
            m = m.reshape((n_chunks, eta))
            init = program.zero(shape, dtype)

            if groups:
                g = gids.astype(jnp.int32).reshape((n_chunks, eta))

                def gbody(carry, xs):
                    chunk, cm, cg = xs
                    chunk = chunk.reshape((eta,) + shape)
                    gm = (cg[None, :] == jnp.arange(groups)[:, None]) \
                        & cm[None, :]
                    return program.merge(carry,
                                         program.map_chunk(chunk, gm)), None

                partial, _ = jax.lax.scan(gbody, init, (v, m, g))
                return partial

            def body(carry, xs):
                chunk, cm = xs
                chunk = chunk.reshape((eta,) + shape)
                return program.merge(carry, program.map_chunk(chunk, cm)), None

            partial, _ = jax.lax.scan(body, init, (v, m))
            return partial

        if groups:
            if masked:
                return jax.jit(fold)
            return jax.jit(lambda block, gids: fold(block, None, gids))
        if masked:
            return jax.jit(lambda block, mask: fold(block, mask, None))
        return jax.jit(lambda block: fold(block, None, None))

    def fold_block(
        self,
        program: MapReduceProgram,
        block: Any,                      # [rows, ...] device or host array
        mask: Optional[Any],             # [rows] bool; None = every row
        eta: int,
        row_shape: Tuple[int, ...],
        dtype,
        gids: Optional[Any] = None,      # [rows] int32 group ids (grouped)
        num_groups: int = 0,
        owner: Optional[int] = None,     # fault context: owning device index
    ) -> PyTree:
        """Fold one block into a partial — the map phase at block granularity.

        ``block`` committed to a device keeps the fold there (jit follows
        committed inputs), which is the colocation property: the block's
        payload bytes never leave its owner; only the partial will.

        Blocks are padded to the bucketed row count *outside* the jit (pad
        rows masked off), so two regions of 9 and 12 rows share the 16-row
        executable instead of compiling twice.  With ``gids``/``num_groups``
        the fold is group-aware: the partial's leaves carry a leading group
        axis (see :class:`~repro.core.stats.GroupedProgram`).
        """
        if self.fault_injector is not None:
            # fired before any padding/compile work so an injected fold
            # fault costs the caller nothing but the retry itself
            self.fault_injector.fire("fold", device=owner)
        rows = int(block.shape[0])
        grouped = num_groups > 0
        if grouped and gids is None:
            raise ValueError("grouped fold needs per-row group ids")
        bucket = self.bucket_rows(rows)
        if bucket != rows:
            padw = [(0, bucket - rows)]
            block = jnp.pad(block, padw + [(0, 0)] * (block.ndim - 1))
            mask = jnp.pad(jnp.ones((rows,), bool) if mask is None
                           else jnp.asarray(mask, bool), padw)
            if grouped:
                gids = jnp.pad(jnp.asarray(gids, jnp.int32), padw)
        impl = self.fold_path(program, dtype, num_groups)
        with self._count_lock:
            self.fold_path_counts[impl] += 1
        if impl == "pallas":
            # chunk-free: eta is absent from the key — every η shares the
            # one fused-kernel executable per (bucket, G) signature
            key = ("pfold", program.cache_key(), bucket, tuple(row_shape),
                   str(dtype), mask is not None, int(num_groups))
            fn = self._get_or_build(
                key, lambda: self._pallas_fold_fn(
                    program, bucket, row_shape, dtype, mask is not None,
                    groups=int(num_groups)))
        else:
            key = ("bfold", program.cache_key(), bucket, tuple(row_shape),
                   str(dtype), int(eta), mask is not None, int(num_groups))
            fn = self._get_or_build(
                key, lambda: self._block_fold_fn(
                    program, bucket, row_shape, dtype, eta, mask is not None,
                    groups=int(num_groups)))
        if grouped:
            gids = jnp.asarray(gids, jnp.int32)
            return fn(block, mask, gids) if mask is not None \
                else fn(block, gids)
        return fn(block, mask) if mask is not None else fn(block)

    def merge_finalize(
        self,
        program: MapReduceProgram,
        partials: Sequence[PyTree],
        row_shape: Tuple[int, ...],
        dtype,
        owners: Optional[Sequence[Optional[int]]] = None,
        slots: Optional[int] = None,
        record: Optional[MergeRecord] = None,
    ) -> PyTree:
        """Reduce phase: combine the per-block partials and finalize.

        Two physical reduces share this entry point:

        - **tree** — additive programs on a 1-D data mesh with ``owners``
          given: each owner device pre-merges its own partials locally (no
          payload crosses the interconnect), the D per-device sums join via
          one ``psum`` over the data axis (the ICI's hardware all-reduce —
          log-depth, all links busy), and finalize runs replicated.  The
          merge wall stops scaling with #blocks-on-one-device.
        - **funnel** — the fallback (non-additive merges, single device,
          exotic meshes, ``merge_strategy="funnel"``): partials move to one
          device and a jitted merge+finalize reduces them there.

        Zero partials finalize the monoid identity (the empty-selection
        result).  Funnel executables are keyed by the partial count rounded
        up to a power of two (identity-padded), so drifting block counts
        don't multiply compiles.  ``slots`` fixes the width of the tree's
        owner-local sums (the most blocks one owner holds under the
        placement), so every query's sums share one executable per device;
        without it the width is the busiest owner's partial count rounded
        up to a power of two.  ``record`` receives the path taken and the
        partial bytes moved between chips; the return value is the
        finalized result alone.
        """
        if record is None:
            record = MergeRecord()
        if self._tree_merge_ok(program, partials, owners):
            record.path = "tree"
            with self._count_lock:
                self.merge_path_counts["tree"] += 1
            return self._merge_tree(program, partials, owners,
                                    row_shape, dtype, slots, record)
        record.path = "funnel"
        with self._count_lock:
            self.merge_path_counts["funnel"] += 1
        return self._merge_funnel(program, partials, row_shape, dtype,
                                  record)

    def _tree_merge_ok(self, program, partials, owners) -> bool:
        return (self.merge_strategy == "auto"
                and program.additive
                and self._axis_devices is not None
                and len(self._axis_devices) > 1
                and owners is not None
                and len(owners) == len(partials)
                and len(partials) > 1
                and all(o is not None and 0 <= o < len(self._axis_devices)
                        for o in owners))

    def _presum_fn(self, program, count: int, row_shape, dtype):
        """One jitted per-device sum over ``count`` stacked partials — the
        owner-local pre-merge of the tree reduce, its leaves given a
        leading axis of one (the owner's row of the all-reduce's input)."""
        key = ("bpresum", program.cache_key(), int(count), tuple(row_shape),
               str(dtype))

        def build():
            def presum(*ps):
                ops = _checked_merge_ops(program, ps[0])
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ps)
                return _combine_leafwise(
                    stacked, ops, lambda s: s.sum(axis=0, keepdims=True),
                    lambda s: s.max(axis=0, keepdims=True))

            return jax.jit(presum)

        return self._get_or_build(key, build)

    def _identity(self, program, row_shape, dtype, dev) -> PyTree:
        """The program's identity partial, committed to ``dev`` once."""
        key = (program.cache_key(), tuple(row_shape), str(dtype), dev.id)
        ident = self._identities.get(key)
        if ident is None:
            ident = jax.device_put(program.zero(tuple(row_shape), dtype), dev)
            self._identities.put(key, ident)
        return ident

    def _merge_tree(self, program, partials, owners, row_shape, dtype,
                    slots: Optional[int], record: MergeRecord):
        """psum-over-mesh reduce: owner-local pre-merge, one all-reduce.

        Every owner sums a stack of one width, its partials padded with
        identity partials, so a query's presums never compile however its
        selected blocks fall over the owners."""
        D = len(self._axis_devices)
        by_owner: List[List[PyTree]] = [[] for _ in range(D)]
        for p, o in zip(partials, owners):
            by_owner[o].append(p)
        busiest = max(len(ps) for ps in by_owner)
        width = (slots if slots is not None and busiest <= slots
                 else self._next_pow2(busiest))
        presum = self._presum_fn(program, width, row_shape, dtype)

        with spans.span("merge.presum"):
            shards = []
            for d, ps in enumerate(by_owner):
                dev = self._axis_devices[d]
                # partials folded this execution already live on device d;
                # cached partials from a pre-rebalance owner re-home here
                # (tiny — a partial, never a payload block)
                record.cross_chip_bytes += sum(_off_device_bytes(p, dev)
                                               for p in ps)
                moved = [jax.device_put(p, dev) for p in ps]
                moved.extend([self._identity(program, row_shape, dtype, dev)]
                             * (width - len(moved)))
                shards.append(presum(*moved))

        with spans.span("merge.allreduce"):
            sharding = NamedSharding(self.mesh, P(self.data_axis))

            def assemble(*leaves):
                shape = (D,) + tuple(leaves[0].shape[1:])
                return jax.make_array_from_single_device_arrays(
                    shape, sharding, list(leaves))

            stacked = jax.tree.map(assemble, *shards)
            fn = self._allreduce_fn(program, row_shape, dtype)
            with self._collective_lock:
                return fn(stacked)

    def _allreduce_fn(self, program, row_shape, dtype):
        """The tree merge's one program over the mesh: the owners' sums,
        stacked ``[D, ...]`` along the data axis, joined by one ``psum``
        (``pmax`` for max leaves) and finalized on every device."""
        key = ("btree", program.cache_key(), tuple(row_shape), str(dtype))

        def build():
            def local(t):
                ops = _checked_merge_ops(program, t)
                return _combine_leafwise(
                    t, ops,
                    lambda x: jax.lax.psum(x[0], self.data_axis),
                    lambda x: jax.lax.pmax(x[0], self.data_axis))

            reduce_fn = shard_map_compat(
                local, mesh=self.mesh, in_specs=P(self.data_axis),
                out_specs=P(), check=False)
            return jax.jit(lambda t: program.finalize(reduce_fn(t)))

        return self._get_or_build(key, build)

    def _merge_funnel(self, program, partials, row_shape, dtype,
                      record: MergeRecord):
        """Single-device reduce: partials meet on the merge device and one
        jitted merge+finalize combines them (count bucketed to a power of
        two with identity partials, so the executable key space stays
        narrow as block counts drift)."""
        n = len(partials)
        bucket = n if n <= 1 else self._next_pow2(n)
        key = ("bmerge", program.cache_key(), bucket, tuple(row_shape),
               str(dtype))

        def build():
            shape = tuple(row_shape)

            def mf(*ps):
                if not ps:
                    acc = program.zero(shape, dtype)
                elif program.additive and len(ps) > 1:
                    ops = _checked_merge_ops(program, ps[0])
                    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ps)
                    acc = _combine_leafwise(stacked, ops,
                                            lambda s: s.sum(axis=0),
                                            lambda s: s.max(axis=0))
                else:
                    items: List[PyTree] = list(ps)
                    while len(items) > 1:
                        items = [
                            program.merge(items[i], items[i + 1])
                            if i + 1 < len(items) else items[i]
                            for i in range(0, len(items), 2)
                        ]
                    acc = items[0]
                return program.finalize(acc)

            return jax.jit(mf)

        fn = self._get_or_build(key, build)
        dev = self._merge_device
        if self.mesh.devices.size > 1:
            record.cross_chip_bytes += sum(_off_device_bytes(p, dev)
                                           for p in partials)
        moved = [jax.device_put(p, dev) for p in partials]
        if bucket > n:
            identity = jax.device_put(
                program.zero(tuple(row_shape), dtype), dev)
            moved.extend([identity] * (bucket - n))
        return fn(*moved)

    def partial_nbytes(self, program: MapReduceProgram,
                       row_shape: Tuple[int, ...], dtype) -> int:
        """Bytes of one partial (the unit of reduce-phase shuffle traffic).
        Cached outside the executable LRU — shape arithmetic is not a
        compile, so it must not move ``compile_count``."""
        key = (program.cache_key(), tuple(row_shape), str(dtype))
        nbytes = self._partial_bytes.get(key)
        if nbytes is None:
            tree = jax.eval_shape(
                lambda: program.zero(tuple(row_shape), dtype))
            nbytes = sum(
                int(np.prod(x.shape, dtype=np.int64)) * x.dtype.itemsize
                for x in jax.tree.leaves(tree))
            self._partial_bytes[key] = nbytes
        return nbytes

    def fold_cost(
        self,
        program: MapReduceProgram,
        rows: int,
        row_shape: Tuple[int, ...],
        dtype,
        eta: int,
        masked: bool = False,
        groups: int = 0,
    ) -> Mapping[str, float]:
        """XLA ``cost_analysis`` of the per-block fold executable (FLOPs /
        bytes accessed) — the oracle the CSE bench and property test use to
        show shared accumulators are computed once per chunk, and the
        measured bytes-read the fused-kernel bench compares its one-pass
        analytic bytes against (grouped folds via ``groups > 0``)."""
        fn = self._block_fold_fn(program, rows, row_shape, dtype, eta,
                                 masked, groups=int(groups))
        args = [jax.ShapeDtypeStruct((rows,) + tuple(row_shape),
                                     jnp.dtype(dtype))]
        if masked:
            args.append(jax.ShapeDtypeStruct((rows,), jnp.dtype(bool)))
        if groups:
            args.append(jax.ShapeDtypeStruct((rows,), jnp.dtype(jnp.int32)))
        cost = fn.lower(*args).compile().cost_analysis()
        return {"flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0))}

    # ------------------------------------------------------------------

    def run(
        self,
        program: MapReduceProgram,
        values: jax.Array,
        valid: jax.Array,
        chunk_size: int,
        row_mask: Optional[jax.Array] = None,
    ) -> Tuple[PyTree, MapReduceStats]:
        """Run ``program`` over a colocated ``[D, C, ...]`` layout.

        ``row_mask`` (``[D, C]`` bool) restricts the fold to a query subset
        (the §2.3 path: the mask comes from index columns, and the payload
        rows it deselects are never read by the fold — locality preserved
        because mask and payload share the row layout).
        """
        D, C = values.shape[0], values.shape[1]
        if C % chunk_size != 0:
            pad = -C % chunk_size
            values = jnp.pad(values, [(0, 0), (0, pad)] + [(0, 0)] * (values.ndim - 2))
            valid = jnp.pad(valid, [(0, 0), (0, pad)])
            if row_mask is not None:
                row_mask = jnp.pad(row_mask, [(0, 0), (0, pad)])
            C += pad
        mask = valid if row_mask is None else (valid & row_mask)

        row_shape = tuple(values.shape[2:])
        dtype = values.dtype
        key = (program.cache_key(), row_shape, str(dtype), chunk_size, C)
        fn = self._get_or_build(
            key, lambda: self._build(program, row_shape, dtype, chunk_size))
        with self._collective_lock:
            result = fn(values, mask)

        # --- byte accounting (host-side; mask is tiny) -------------------
        mask_np = np.asarray(jax.device_get(mask))
        per_dev_rows = mask_np.sum(axis=1)
        row_nbytes = int(np.prod(row_shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        partial = program.zero(row_shape, dtype)
        partial_bytes = sum(
            int(np.prod(jnp.shape(x), dtype=np.int64)) * jnp.result_type(x).itemsize
            for x in jax.tree.leaves(partial)
        )
        chunks_per_dev = np.ceil(per_dev_rows / chunk_size).astype(np.int64)
        shuffle = partial_bytes * (D if program.additive else D * D)  # psum vs all_gather
        stats = MapReduceStats(
            local_rows_read=int(per_dev_rows.sum()),
            local_bytes_read=int(per_dev_rows.sum()) * row_nbytes,
            shuffle_bytes=int(shuffle),
            rounds=C // chunk_size,
            chunks=int(chunks_per_dev.sum()),
            chunk_size=chunk_size,
        )
        return result, stats
