"""Pallas kernel: fused grouped power-sum fold — one HBM pass per block.

This is the fold hot path of the block-granular engine collapsed into a
single streaming kernel.  The XLA lowering of ``shared_map_chunk`` /
``grouped_shared_map_chunk`` (``repro.core.stats``) materializes the masked
cast, each power raise, and a per-power segment-sum as separate passes over
the chunk; the fold is memory-bound (a handful of FLOPs per byte), so every
extra pass is wall-clock.  Here the block's ``[R, F]`` payload crosses
HBM→VMEM exactly once and the full grouped shared-accumulator pool
``(count, Σx, Σx², Σx³, Σx⁴)`` comes out the other side:

- row validity and gid segment assignment are applied IN-KERNEL: the
  ``[BR, G]`` one-hot group weights are built from the gid/mask tiles, and
  rows no group claims are zeroed BEFORE the power raises — preserving the
  engine's NaN/Inf-poisoning guarantee (a poisoned masked-off row must not
  reach the weighted contraction, since ``0 × NaN = NaN``);
- each power of ``x`` is materialized once in VMEM and contracted against
  the group weights with one MXU ``dot_general`` — the grouped CSE, now
  with zero extra HBM traffic;
- accumulators are ``[G, BF]`` fp32 VMEM blocks revisited across the row
  sweep (grid: feature tiles outer, row blocks inner/sequential, init at
  row-block 0) — the same tiling story as the subsumed streaming_stats
  kernel, widened by the group axis.

Ungrouped folds (``G = 1``) take a schedule of their own, because for
them the one-hot contraction is a masked row sum and the padded pool is
seven rows of zeros: :func:`_rowsum_fold_kernel` streams the block through
wide feature tiles (sized by bytes, ``ROWSUM_TILE_BYTES``, so the
per-grid-step cost is paid a few hundred times a block rather than tens of
thousands), sums the masked rows with fp32 adds on the VPU a lane chunk at
a time, and writes an unpadded ``[1, F]`` pool.  The group count the
kernel is given is a static shape, so the schedule is fixed per
executable.

Targeted at TPU (G padded to sublane multiples, BF in 128-lane units).
The grid covers ragged edges instead of padding the block: the last
feature tile's out-of-range columns only ever reach out-of-range output
columns (which are never written back), and the last row block's
out-of-range rows are masked off in-kernel by their row index.  So a
block folds as it stands, with no whole-block pad copy in HBM.  CPU runs
validate the same kernel with ``interpret=True``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_FEATURES = 512
#: the row-sum schedule's input tile: about this many bytes a grid step,
#: so the DMA of a tile outweighs the step's fixed cost, and its double
#: buffer plus the [1, BF] accumulators fit the default scoped VMEM
ROWSUM_TILE_BYTES = 4 * 2**20
#: fp32 elements of one inner row-sum step ([BR, chunk] in vregs): small
#: enough that the masked tile, its square and the sums stay in registers
ROWSUM_CHUNK_ELEMS = 32768

#: canonical accumulator order (mirrors stats.SHARED_ACCUMULATORS — kept
#: literal here so the kernel package does not import the engine)
ACC_ORDER: Tuple[str, ...] = ("count", "s1", "s2", "s3", "s4")


def _fused_fold_kernel(x_ref, g_ref, m_ref, *out_refs,
                       names: Tuple[str, ...], n_groups: int, n_rows: int):
    """One (feature-tile, row-block) grid cell.

    x_ref    [BR, BF]   payload tile (any real dtype; cast to fp32)
    g_ref    [BR, 1]    int32 group ids
    m_ref    [BR, 1]    row validity (float 0/1)
    out_refs             fp32 accumulators in ``names`` order:
                         count [G, 1]; s1..s4 [G, BF] — revisited across the
                         row sweep, initialized at row-block 0
    n_rows               the block's real row count: rows of a ragged last
                         row block at or past it are masked off
    """
    j = pl.program_id(1)  # row-block index (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        for ref in out_refs:
            ref[...] = jnp.zeros_like(ref)

    x = x_ref[...].astype(jnp.float32)             # [BR, BF]
    m = m_ref[...].astype(jnp.float32)             # [BR, 1]
    g = g_ref[...]                                 # [BR, 1] int32
    br = x.shape[0]
    if n_rows % br:
        # ragged last row block: its tail reads past the block's end
        row = j * br + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
        m = jnp.where(row < n_rows, m, 0.0)

    # one-hot group weights: w[r, g] = 1 iff row r is valid AND gid(r) == g
    gid_iota = jax.lax.broadcasted_iota(jnp.int32, (br, n_groups), 1)
    w = jnp.where(g == gid_iota, m, 0.0)           # [BR, G]

    # mask-zero BEFORE the power raises: a NaN/Inf payload in a masked-off
    # row must not poison the contraction (0-weight × NaN is NaN)
    x = jnp.where(m > 0.0, x, 0.0)

    def seg(v):                                    # [BR, X] -> [G, X]
        return jax.lax.dot_general(
            w, v, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    refs = iter(out_refs)
    if "count" in names:
        next(refs)[...] += seg(jnp.ones((br, 1), jnp.float32))
    if "s1" in names:
        next(refs)[...] += seg(x)
    if any(n in names for n in ("s2", "s3", "s4")):
        x2 = x * x
        if "s2" in names:
            next(refs)[...] += seg(x2)
        if "s3" in names:
            next(refs)[...] += seg(x2 * x)
        if "s4" in names:
            next(refs)[...] += seg(x2 * x2)


def rowsum_tiles(rows: int, features: int, itemsize: int,
                 block_rows: int = DEFAULT_BLOCK_ROWS,
                 block_features: Optional[int] = None) -> Tuple[int, int, int]:
    """-> ``(BR, BF, chunk)`` of the row-sum schedule for an ``[R, F]``
    block: the row tile, the feature tile (about ``ROWSUM_TILE_BYTES`` of
    input unless ``block_features`` fixes it) and the lanes of one inner
    step, which divides ``BF``.  A feature count below one chunk is taken
    whole; otherwise ``BF`` is a multiple of the chunk no wider than the
    block, and the grid covers the ragged last tile."""
    br = min(block_rows, rows)
    # VMEM and vregs hold rows in whole sublane tiles: 8 rows of 32 bits,
    # 16 of 16 bits, 32 of 8 bits (the fp32 cast takes 8-row tiles)
    pack = 8 * max(1, 4 // itemsize)
    in_rows = -(-br // pack) * pack
    chunk = max(128, ROWSUM_CHUNK_ELEMS // (-(-br // 8) * 8) // 128 * 128)
    if block_features is None:
        bf = max(chunk,
                 ROWSUM_TILE_BYTES // (in_rows * itemsize) // chunk * chunk)
    else:
        if block_features % 128:
            raise ValueError(f"block_features {block_features} is not a "
                             f"multiple of 128 lanes")
        bf = block_features
        chunk = min(chunk, bf)
        while bf % chunk:
            chunk -= 128
    if features < chunk:
        return br, features, features
    return br, min(bf, features // chunk * chunk), chunk


def _rowsum_fold_kernel(x_ref, g_ref, m_ref, *out_refs,
                        names: Tuple[str, ...], n_rows: int, chunk: int):
    """One (feature-tile, row-tile) grid cell of the ``G = 1`` schedule.

    x_ref    [BR, BF]   payload tile (any real dtype; cast to fp32)
    g_ref    [BR, 1]    int32 group ids (a row counts only in group 0)
    m_ref    [BR, 1]    row validity (float 0/1)
    out_refs             fp32 accumulators in ``names`` order: count
                         [1, 1], summed at feature tile 0 only; s1..s4
                         [1, BF], one row each, accumulated across the row
                         sweep (initialized at row tile 0 when it has more
                         than one tile)
    n_rows               the block's real row count: rows of a ragged last
                         row tile at or past it are masked off
    chunk                lanes a step of the inner loop sums
    """
    i = pl.program_id(0)  # feature tile
    j = pl.program_id(1)  # row tile (innermost, sequential)
    br, bf = x_ref.shape
    one_tile = n_rows <= br

    m = m_ref[...].astype(jnp.float32)             # [BR, 1]
    if n_rows % br:
        row = j * br + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
        m = jnp.where(row < n_rows, m, 0.0)
    w = jnp.where(g_ref[...] == 0, m, 0.0)         # [BR, 1] row weights

    refs = dict(zip(names, out_refs))
    wide = [refs[n] for n in names if n != "count"]

    def put(ref, idx, v):
        if one_tile:
            ref[idx] = v
        else:
            ref[idx] += v

    if "count" in refs:
        # the count block is shared by every feature tile: sum it once
        @pl.when(i == 0)
        def _count():
            if not one_tile:
                @pl.when(j == 0)
                def _init():
                    refs["count"][...] = jnp.zeros((1, 1), jnp.float32)
            put(refs["count"], (slice(None), slice(None)),
                jnp.sum(w, axis=0, keepdims=True))

    if not one_tile:
        @pl.when(j == 0)
        def _init():
            for ref in wide:
                ref[...] = jnp.zeros_like(ref)

    def step(cols):
        x = x_ref[:, cols].astype(jnp.float32)     # [BR, chunk]
        # mask-zero BEFORE the power raises: a NaN/Inf payload in a
        # masked-off row must not reach the sums
        x = jnp.where(w > 0.0, x, 0.0)
        pows = [x] if "s1" in names else []
        if any(n in names for n in ("s2", "s3", "s4")):
            x2 = x * x
            pows += [p for n, p in (("s2", x2), ("s3", x2 * x),
                                    ("s4", x2 * x2)) if n in names]
        for ref, p in zip(wide, pows):
            put(ref, (slice(None), cols), jnp.sum(p, axis=0, keepdims=True))

    n_chunks = bf // chunk
    if n_chunks == 1:
        step(slice(None))
    else:
        def body(k, carry):
            step(pl.ds(pl.multiple_of(k * chunk, chunk), chunk))
            return carry

        jax.lax.fori_loop(0, n_chunks, body, 0)


def _rowsum_fold(x, g2, m2, names, block_rows, block_features, interpret):
    """The ``G = 1`` schedule's ``pallas_call``: count [1, 1], s_k [1, F]."""
    R, F = x.shape
    br, bf, chunk = rowsum_tiles(R, F, x.dtype.itemsize, block_rows,
                                 block_features)
    out_specs, out_shape = [], []
    for n in names:
        if n == "count":
            out_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0)))
            out_shape.append(jax.ShapeDtypeStruct((1, 1), jnp.float32))
        else:
            out_specs.append(pl.BlockSpec((1, bf), lambda i, j: (0, i)))
            out_shape.append(jax.ShapeDtypeStruct((1, F), jnp.float32))
    return pl.pallas_call(
        functools.partial(_rowsum_fold_kernel, names=names, n_rows=R,
                          chunk=chunk),
        grid=(pl.cdiv(F, bf), pl.cdiv(R, br)),
        in_specs=[
            pl.BlockSpec((br, bf), lambda i, j: (j, i)),
            pl.BlockSpec((br, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, g2, m2)


@functools.partial(
    jax.jit,
    static_argnames=("names", "n_groups", "block_rows", "block_features",
                     "interpret"))
def fused_fold_pallas(
    x: jax.Array,            # [R, F] — any R, F (ragged edges are masked)
    gids: jax.Array,         # [R] int32
    mask: jax.Array,         # [R] float 0/1
    names: Tuple[str, ...],
    n_groups: int,           # 1, or sublane-padded by the ops wrapper
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_features: Optional[int] = None,   # None: the schedule's own
    interpret: bool = False,
):
    """-> accumulators in ``names`` order: count [G, 1], s_k [G, F] (fp32).

    ``n_groups == 1`` takes the row-sum schedule (:func:`_rowsum_fold`,
    feature tiles sized by bytes); any other G the one-hot contraction on
    ``block_features`` lanes (512 by default), where the ``count`` block is
    shared across feature tiles: each tile's row sweep re-initializes and
    re-accumulates it, so the final value is exact (same trick as the
    streaming_stats kernel this one subsumes).
    """
    R, F = x.shape
    g2 = gids.reshape(R, 1).astype(jnp.int32)
    m2 = mask.reshape(R, 1).astype(jnp.float32)
    if n_groups == 1:
        return _rowsum_fold(x, g2, m2, names, block_rows, block_features,
                            interpret)

    br = min(block_rows, R)
    bf = min(block_features or DEFAULT_BLOCK_FEATURES, F)
    grid = (pl.cdiv(F, bf), pl.cdiv(R, br))

    out_specs = []
    out_shape = []
    for n in names:
        if n == "count":
            out_specs.append(pl.BlockSpec((n_groups, 1), lambda i, j: (0, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((n_groups, 1), jnp.float32))
        else:
            out_specs.append(
                pl.BlockSpec((n_groups, bf), lambda i, j: (0, i)))
            out_shape.append(
                jax.ShapeDtypeStruct((n_groups, F), jnp.float32))

    return pl.pallas_call(
        functools.partial(_fused_fold_kernel, names=names,
                          n_groups=n_groups, n_rows=R),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bf), lambda i, j: (j, i)),
            pl.BlockSpec((br, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, g2, m2)
