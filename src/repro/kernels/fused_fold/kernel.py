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

Ungrouped folds are the ``G = 1`` degenerate case: every valid row lands in
group 0 and the one-hot weights collapse to the row mask.

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
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_FEATURES = 512

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


@functools.partial(
    jax.jit,
    static_argnames=("names", "n_groups", "block_rows", "block_features",
                     "interpret"))
def fused_fold_pallas(
    x: jax.Array,            # [R, F] — any R, F (ragged edges are masked)
    gids: jax.Array,         # [R] int32
    mask: jax.Array,         # [R] float 0/1
    names: Tuple[str, ...],
    n_groups: int,           # already sublane-padded by the ops wrapper
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_features: int = DEFAULT_BLOCK_FEATURES,
    interpret: bool = False,
):
    """-> accumulators in ``names`` order: count [G, 1], s_k [G, F] (fp32).

    The ``count`` block is shared across feature tiles: each tile's row
    sweep re-initializes and re-accumulates it, so the final value is exact
    (same trick as the streaming_stats kernel this one subsumes).
    """
    R, F = x.shape
    br = min(block_rows, R)
    bf = min(block_features, F)
    grid = (pl.cdiv(F, bf), pl.cdiv(R, br))

    g2 = gids.reshape(R, 1).astype(jnp.int32)
    m2 = mask.reshape(R, 1).astype(jnp.float32)

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
