"""Ahead-of-time compiles of the fold kernel for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described, not attached.  That catches what interpret mode cannot
— tile alignment, the kernel's VMEM budget, and whether a full-width
block's fold fits one chip's HBM — at no chip time.  Shapes are the ones
``chip_smoke.py`` commits: 32-row blocks of MNI152 1 mm volumes
(182 x 218 x 182 float32), flattened to ``[rows, features]`` as
``GridSession`` commits them.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports every test file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from bench.tracefile import KERNEL_RE
from repro.core.mapreduce import MapReduceEngine
from repro.core.stats import FusedProgram, GroupedProgram, \
    HistogramProgram, MeanProgram, VarianceProgram
from repro.kernels.fused_fold.kernel import ACC_ORDER, fused_fold_pallas
from repro.kernels.fused_fold.ops import fused_fold, max_groups_for_vmem
from repro.utils import make_mesh

MNI_SHAPE = (182, 218, 182)
FEATURES = int(np.prod(MNI_SHAPE))
BLOCK_ROWS = 32                 # chip_smoke's committed block (pow2 bucket)
SMOKE_GROUPS = 2                # chip_smoke's group_by("idx:sex")
HBM_BYTES = 16 * 10**9          # one v5e chip
NAMES = ("count", "s1", "s2")   # the fused mean + variance pool


@pytest.fixture(scope="module")
def host_2x2():
    """The four chips of a described v5e 2x2 host."""
    # skip only where the TPU compiler is not installed; any other failure
    # to describe the chip (a held library lock, a compiler regression)
    # fails the test
    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # compiles for a described chip cannot be read back from a persistent
    # cache without the chip: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(host_2x2):
    return SingleDeviceSharding(host_2x2[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes, mem.temp_size_in_bytes


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("groups", [1, SMOKE_GROUPS])
def test_fused_fold_at_full_width_block(one_chip, groups):
    """The op on the committed block: the kernel reads it in place, so
    the only temp is the accumulator pool (sublane-padded when grouped)
    — no relayout or pad copy of the block."""
    R = BLOCK_ROWS
    args = (_spec((R, FEATURES), jnp.float32, one_chip),
            _spec((R,), jnp.bool_, one_chip),
            _spec((R,), jnp.int32, one_chip))
    arg, temp = _compile(
        lambda x, m, g: fused_fold(x, m, g, num_groups=groups, names=NAMES),
        *args)
    block = R * FEATURES * 4
    assert arg >= block
    assert temp < block, (arg, temp)
    assert arg + temp < HBM_BYTES


def test_engine_grouped_fold_at_full_width_block(one_chip):
    """The engine's per-block executable for the smoke's per-sex query,
    including the reshape of the pool back to the volume shape."""
    engine = MapReduceEngine(make_mesh((1,), ("data",)))
    program = GroupedProgram(FusedProgram((MeanProgram(), VarianceProgram())),
                             SMOKE_GROUPS)
    fold = engine._pallas_fold_fn(program, BLOCK_ROWS, MNI_SHAPE,
                                  jnp.float32, masked=True,
                                  groups=SMOKE_GROUPS)
    R = BLOCK_ROWS
    arg, temp = _compile(fold,
                         _spec((R, FEATURES), jnp.float32, one_chip),
                         _spec((R,), jnp.bool_, one_chip),
                         _spec((R,), jnp.int32, one_chip))
    assert arg + temp < HBM_BYTES
    assert temp < R * FEATURES * 4, (arg, temp)


def test_engine_fold_kernel_keeps_the_name_the_benchmark_reads(one_chip):
    """The benchmark finds the fold kernel's device time by its HLO
    instruction (``bench.tracefile.KERNEL_RE``), a name the jitted
    ``fused_fold_pallas`` wrapper gives the custom call: the engine's
    per-block executable for a fused mean + variance subset fold must
    still carry it."""
    engine = MapReduceEngine(make_mesh((1,), ("data",)))
    program = FusedProgram((MeanProgram(), VarianceProgram()))
    fold = engine._pallas_fold_fn(program, BLOCK_ROWS, MNI_SHAPE,
                                  jnp.float32, masked=True)
    text = jax.jit(fold).lower(
        _spec((BLOCK_ROWS, FEATURES), jnp.float32, one_chip),
        _spec((BLOCK_ROWS,), jnp.bool_, one_chip)).compile().as_text()
    ops = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()]
    assert any(KERNEL_RE.match(op) for op in ops), [
        op[:80] for op in ops if "custom-call" in op]


def test_engine_ungrouped_fold_writes_an_unpadded_pool(one_chip):
    """The benchmark's fold (a masked fused mean + variance, no group-by)
    takes the kernel's row-sum schedule: its pool is one [1, F] row a
    power, so the kernel writes 2·F·4 bytes plus its count, and the
    engine's executable needs no padded [8, F] pool as temp.  Its output
    is the partial in the volume's tiled device layout, whose minor dims
    (182, 218) pad to (184, 256): 18.7% above the logical bytes."""
    R = BLOCK_ROWS
    pool = 2 * FEATURES * 4
    op = jax.jit(lambda x, m: fused_fold(x, m, names=NAMES)).lower(
        _spec((R, FEATURES), jnp.float32, one_chip),
        _spec((R,), jnp.bool_, one_chip)).compile().memory_analysis()
    assert op.output_size_in_bytes <= pool + 4096, op
    assert op.temp_size_in_bytes < 10**8, op
    engine = MapReduceEngine(make_mesh((1,), ("data",)))
    program = FusedProgram((MeanProgram(), VarianceProgram()))
    fold = engine._pallas_fold_fn(program, R, MNI_SHAPE, jnp.float32,
                                  masked=True)
    mem = jax.jit(fold).lower(
        _spec((R, FEATURES), jnp.float32, one_chip),
        _spec((R,), jnp.bool_, one_chip)).compile().memory_analysis()
    assert mem.output_size_in_bytes <= 1.19 * pool + 4096, mem
    assert mem.temp_size_in_bytes < 10**8, mem


@pytest.mark.parametrize("rows,dtype,names", [
    (BLOCK_ROWS, jnp.float32, ACC_ORDER),
    (BLOCK_ROWS, jnp.bfloat16, ACC_ORDER),
    (1, jnp.float32, NAMES),
], ids=["f32-all-powers", "bf16-all-powers", "one-row"])
def test_rowsum_schedule_fits_vmem_at_full_width(one_chip, rows, dtype,
                                                 names):
    """The row-sum schedule's byte-sized tiles fit the default scoped
    VMEM for every accumulator set, packed dtypes and a one-row block
    (whose tile pads to a whole sublane tile)."""
    arg, temp = _compile(
        lambda x, g, m: fused_fold_pallas(x, g, m, names, 1),
        _spec((rows, FEATURES), dtype, one_chip),
        _spec((rows,), jnp.int32, one_chip),
        _spec((rows,), jnp.float32, one_chip))
    assert temp == 0, temp
    assert arg + temp < HBM_BYTES


@pytest.mark.parametrize("program", [
    MeanProgram(), HistogramProgram(bins=8),
    GroupedProgram(FusedProgram((MeanProgram(), VarianceProgram())),
                   SMOKE_GROUPS)], ids=["mean", "histogram", "grouped"])
def test_engine_xla_fold_at_full_width_block(one_chip, program):
    """The XLA chunk-scan fold reads the same flat committed block: it
    restores the row shape one chunk at a time, so its temp is a few
    chunks' working set, never a relayout copy of the whole block."""
    engine = MapReduceEngine(make_mesh((1,), ("data",)), fold_impl="xla")
    R, eta = BLOCK_ROWS, 8
    groups = SMOKE_GROUPS if isinstance(program, GroupedProgram) else 0
    fold = engine._block_fold_fn(program, R, MNI_SHAPE, jnp.float32, eta,
                                 masked=True, groups=groups)
    args = [_spec((R, FEATURES), jnp.float32, one_chip),
            _spec((R,), jnp.bool_, one_chip)]
    if groups:
        args.append(_spec((R,), jnp.int32, one_chip))
    mem = jax.jit(fold).lower(*args).compile().memory_analysis()
    temp = mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + temp < HBM_BYTES
    assert temp < R * FEATURES * 4, temp


@pytest.mark.parametrize("names", [NAMES, ACC_ORDER])
def test_fused_fold_pallas_at_vmem_group_limit(one_chip, names):
    """The largest G the engine sends to the kernel still fits its VMEM."""
    G = max_groups_for_vmem(names=names)
    assert G > 8
    R, F = 256, 4096
    arg, temp = _compile(
        lambda x, g, m: fused_fold_pallas(x, g, m, names, G),
        _spec((R, F), jnp.float32, one_chip),
        _spec((R,), jnp.int32, one_chip),
        _spec((R,), jnp.float32, one_chip))
    assert arg + temp < HBM_BYTES


def test_tree_merge_at_full_width_on_a_2x2_host(host_2x2):
    """The four-chip cell's merge at the full 1 mm width: a chip's 8-wide
    owner-local sum reads its partials in place (no temp), and the sums
    meet in one all-reduce over the 2x2 mesh whose only output is the
    replicated answer."""
    engine = MapReduceEngine(Mesh(np.array(host_2x2), ("data",)))
    program = FusedProgram((MeanProgram(), VarianceProgram()))
    zero = jax.eval_shape(lambda: program.zero(MNI_SHAPE, jnp.float32))
    owner = SingleDeviceSharding(host_2x2[1])
    partial = jax.tree.map(lambda a: _spec(a.shape, a.dtype, owner), zero)
    presum = engine._presum_fn(program, 8, MNI_SHAPE, jnp.float32)
    mem = presum.lower(*[partial] * 8).compile().memory_analysis()
    assert mem.temp_size_in_bytes == 0, mem
    assert mem.argument_size_in_bytes >= 8 * 2 * FEATURES * 4, mem
    assert mem.output_size_in_bytes < 1.19 * 2 * FEATURES * 4 + 4096, mem

    rows = NamedSharding(engine.mesh, PartitionSpec("data"))
    stacked = jax.tree.map(
        lambda a: _spec((len(host_2x2),) + a.shape, a.dtype, rows), zero)
    compiled = engine._allreduce_fn(program, MNI_SHAPE,
                                    jnp.float32).lower(stacked).compile()
    text = compiled.as_text()
    assert len([ln for ln in text.splitlines()
                if " all-reduce(" in ln]) == 1, text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0, mem
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        < HBM_BYTES // 10, mem
