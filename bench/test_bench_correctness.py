"""The comparison that decides ``correct`` fails when it should: the
control (the reference one precision step below the configuration's)
falls outside the limits, and a run whose timed path is broken underneath
the harness reads ``correct`` false.  CPU sizes; the chip readings that
set the limits are in PERF.md."""

import numpy as np
import pytest

from bench import cohort, harness, reference, traffic
from bench.test_bench_harness import CELLS, run_cell

#: small volumes per configuration (the control's gaps grow with the
#: number of voxels, so these are larger than the run-path tests' volume)
SIZES = {"t1_mni152_1mm": (16, 16, 16), "t1_mni152_2mm": (16, 16, 16)}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_a_limit(name, seed):
    cell = harness.Cell.find(name)
    c = cohort.build(cell.config, seed, SIZES[cell.workload["config"]])
    qs = traffic.schedule(cell.mix, cell.rate, 20.0, seed)
    masks = np.stack([traffic.mask_of(q, c.ages, c.sexes) for q in qs])
    sel = masks[traffic.sample(qs, masks, int(cell.mix["sample"]), seed)]
    count, mean, var = reference.float64_stats(c.data, sel)
    ctl = reference.control_readings(*reference.control_stats(c.data, sel),
                                     count, mean, var)
    assert not reference.within(ctl, cell.limits), ctl


def _drop_half_the_rows(run):
    session = run.session
    scan = session._scan_mask

    def half(plan):
        mask, qstats, regions = scan(plan)
        keep = np.flatnonzero(mask)[::2]
        out = np.zeros_like(mask)
        out[keep] = True
        return out, qstats, regions

    session._scan_mask = half


def _alter_one_voxel(run):
    import jax.numpy as jnp

    engine = run.session.engine
    merge = engine.merge_finalize

    def altered(*args, **kwargs):
        mean, var = merge(*args, **kwargs)
        bump = jnp.zeros(mean.size, mean.dtype).at[0].set(1e-3)
        return mean + bump.reshape(mean.shape), var

    engine.merge_finalize = altered


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_drop_half_the_rows, _alter_one_voxel],
                         ids=["half_rows_left_out", "answer_altered"])
def test_broken_timed_path_is_not_correct(fault, name):
    res = run_cell(name, tamper=fault)
    assert res["correct"] is False
    read = {k: c["value"] for k, c in res["checks"].items()}
    assert not reference.within(read, harness.Cell.find(name).limits)
