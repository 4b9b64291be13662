"""Public op: fused grouped power-sum fold over a block of rows.

Handles arbitrary row shapes (flattens features), pads groups of a grouped
fold to a sublane multiple (padded groups receive no rows; an ungrouped
fold's pool stays one row), dispatches to the Pallas kernel,
and exposes the analytic cost and VMEM-budget helpers the engine's
``fold_path`` dispatch and the roofline probe consult.  Rows and features
are never padded: the kernel's grid covers ragged edges itself, so a
block is read in place rather than copied to a tile multiple first.

The op's contract is the CSE shared-accumulator pool of
``repro.core.stats``: ``{name: array}`` with ``count`` of shape ``[G]`` and
``s1..s4`` of shape ``[G, *feature_shape]``, all fp32 — exactly what
``FusedProgram``/``GroupedProgram`` partials hold, so the engine can wrap a
kernel result into a cacheable partial without reshuffling.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.chunk_model import VMEM_BYTES
from repro.kernels.fused_fold.kernel import (
    ACC_ORDER,
    DEFAULT_BLOCK_FEATURES,
    DEFAULT_BLOCK_ROWS,
    fused_fold_pallas,
)

#: the kernel's two schedules, named by how a fold combines its rows
ROWSUM, ONEHOT = "rowsum", "onehot"


def canonical_names(names: Tuple[str, ...]) -> Tuple[str, ...]:
    """Validate and order accumulator names along ``ACC_ORDER``."""
    bad = set(names) - set(ACC_ORDER)
    if bad:
        raise ValueError(f"unknown shared accumulators {sorted(bad)}; "
                         f"supported: {ACC_ORDER}")
    if not names:
        raise ValueError("fused_fold needs at least one accumulator name")
    return tuple(n for n in ACC_ORDER if n in set(names))


def fold_schedule(num_groups: int) -> str:
    """Which schedule of the kernel folds ``num_groups`` groups: the VPU
    row sum for one group, the one-hot MXU contraction for more."""
    return ROWSUM if max(1, int(num_groups)) == 1 else ONEHOT


def _pool_groups(num_groups: int) -> int:
    """Rows of the pool the kernel writes: one for the row-sum schedule;
    otherwise the groups padded to an fp32 sublane multiple (min tile is
    8 rows)."""
    if fold_schedule(num_groups) == ROWSUM:
        return 1
    return max(8, -(-int(num_groups) // 8) * 8)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "names", "block_rows", "block_features",
                     "interpret"))
def fused_fold(
    rows: jax.Array,                 # [R, *feature_shape]
    mask: Optional[jax.Array] = None,   # [R] bool/float; None = all valid
    gids: Optional[jax.Array] = None,   # [R] int32; None = all group 0
    num_groups: int = 1,
    names: Tuple[str, ...] = ACC_ORDER,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_features: Optional[int] = None,   # None: the schedule's own
    interpret: bool = False,         # True only for CPU runs and tests
) -> Dict[str, jax.Array]:
    """-> ``{name: acc}``: count ``[G]``, s_k ``[G, *feature_shape]`` fp32.

    One streaming pass over the block, whatever ``G`` or how many
    accumulators were asked for.  Rows are cast to fp32 in VMEM (bf16/int32
    payloads welcome); accumulation is fp32 throughout.
    """
    names = canonical_names(names)
    G = max(1, int(num_groups))
    R = rows.shape[0]
    fshape = rows.shape[1:]
    x = rows.reshape(R, -1)
    F = x.shape[1]

    m = (jnp.ones((R,), jnp.float32) if mask is None
         else mask.astype(jnp.float32))
    g = (jnp.zeros((R,), jnp.int32) if gids is None
         else gids.astype(jnp.int32))

    Gp = _pool_groups(G)

    outs = fused_fold_pallas(x, g, m, names, Gp, block_rows, block_features,
                             interpret=interpret)
    result: Dict[str, jax.Array] = {}
    for n, o in zip(names, outs):
        if n == "count":
            result[n] = o[:G, 0]
        else:
            result[n] = o[:G].reshape((G,) + fshape)
    return result


# ----------------------------------------------------------------------
# analytic cost model (roofline probe + engine dispatch)
# ----------------------------------------------------------------------

def kernel_hbm_bytes(rows: int, features: int, itemsize: int,
                     names: Tuple[str, ...], num_groups: int = 1) -> int:
    """HBM bytes one kernel launch moves: the payload ONCE, the per-row
    mask/gid sidecars, and the accumulator write-back (one row a power
    for an ungrouped fold, the sublane-padded groups otherwise).  This is
    the one-pass contract the bench checks XLA's measured fold bytes
    against."""
    names = canonical_names(names)
    G = _pool_groups(num_groups)
    out = sum(G * 4 if n == "count" else G * features * 4 for n in names)
    return rows * features * itemsize + rows * (4 + 4) + out


def kernel_flops(rows: int, features: int,
                 names: Tuple[str, ...], num_groups: int = 1) -> int:
    """FLOPs per launch: per accumulator one [BR,G]×[BR,X] contraction
    (2·R·X·G each), or for an ungrouped fold one add a row and column
    (R·X each), plus the elementwise power raises and weight build."""
    names = canonical_names(names)
    G = _pool_groups(num_groups)
    per_elem = 1 if G == 1 else 2 * G
    f = 0
    for n in names:
        f += per_elem * rows * (1 if n == "count" else features)
    n_pows = sum(1 for n in names if n != "count")
    # x², x³, x⁴ elementwise products + mask/where + one-hot compare
    f += rows * features * max(0, n_pows - 1)
    f += rows * features + rows * G
    return f


def max_groups_for_vmem(
    names: Tuple[str, ...] = ACC_ORDER,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_features: int = DEFAULT_BLOCK_FEATURES,
    vmem_bytes: float = VMEM_BYTES,
) -> int:
    """Largest G (a sublane multiple, so the kernel gets it unpadded) whose
    kernel fits the per-core scoped VMEM — the engine falls back to the
    XLA fold above this.  Counts what the kernel holds in VMEM at once:

    - fixed: the input tile, double-buffered, plus its fp32 cast and
      square (fp32 worst case), and the double-buffered gid/mask tiles,
      lane-padded to 128;
    - per group: every wide accumulator block, double-buffered; one
      ``[G, BF]`` contraction result; the one-hot weight column; the
      lane-padded count row.

    Checked against the TPU compiler for v5e, which refuses a G a few
    percent above this limit (``tests/test_tpu_compile.py`` compiles at
    it)."""
    names = canonical_names(names)
    n_wide = sum(1 for n in names if n != "count")
    br, bf = block_rows, block_features
    fixed = 4 * br * bf * 4 + 2 * 2 * br * 128 * 4
    per_group = 4 * (2 * n_wide * bf + bf + br + 256)
    budget = vmem_bytes - fixed
    if budget <= 0:
        return 0
    return int(budget // per_group) // 8 * 8
