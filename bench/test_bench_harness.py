"""CPU rehearsal of the on-chip benchmark: every cell's run path at a tiny
volume with the fused kernel in interpret mode, the harness's lookups by
name, the byte count, the seed's meaning, and the refusal without a TPU.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import cohort, harness, reference, traffic, work
from bench.peaks import peak_for

ROOT = harness.ROOT
SPEC = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]
TINY = (4, 5, 6)
BIG_SEED = 2**31 + 12345
TRACE = os.path.join(harness.BENCH, "testdata", "trace")


def run_cell(name, trace=False, seconds=2.0, tamper=None, seed=BIG_SEED):
    import jax

    return harness.execute(harness.Cell.find(name), seed, seconds, trace,
                           t_start=0.0, devices=jax.devices()[:1],
                           shape=TINY, interpret=True, tamper=tamper)


def well_formed(res):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    json.loads(json.dumps(res))
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("name", CELLS)
def test_cell_run_path_prints_every_end_to_end_metric(name, capsys):
    res = run_cell(name)
    well_formed(res)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"]: m["unit"] for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == want
    assert all(math.isfinite(v["value"]) for v in res["metrics"].values())
    # the compared numbers are the last lines of standard error
    err = capsys.readouterr().err.strip().splitlines()
    tail = err[-len(res["checks"]):]
    assert [ln.split()[2] for ln in tail] == list(res["checks"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_traced_run_reads_every_per_layer_metric(name, monkeypatch):
    # no device plane on the CPU: the reducer reads the committed chip trace
    recorded = harness.tracefile.reduce_trace(TRACE)
    monkeypatch.setattr(harness.tracefile, "reduce_trace",
                        lambda path, inflight=(): recorded)
    res = run_cell(name, trace=True)
    well_formed(res)
    assert res["correct"], res["checks"]
    want = {m["name"]: m["unit"] for m in SPEC["per_layer"]
            if name in m.get("workloads", [name])}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == want
    assert res["metrics"]["fold.compiles_in_window"]["value"] == 0
    assert res["metrics"]["blockstore.blocks_transferred"]["value"] == 0
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(res["breakdown"][key]) <= 10


def test_harness_finds_files_by_name():
    for name in CELLS:
        cell = harness.Cell.find(name)
        assert cell.rate > 0 and cell.config["name"] == cell.workload[
            "config"]
        for kind in ("end_to_end", "per_layer"):
            assert cell.metrics(kind)
    for m in SPEC["per_layer"]:
        assert callable(harness.load_metric(m["name"]))
    for c in SPEC["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    with pytest.raises(KeyError):
        harness.Cell.find("no_such.cell")


def test_selected_bytes_count_from_shapes():
    feats = 182 * 218 * 182
    acc = 4 + 2 * 4 * feats                   # count, s1, s2
    assert work.accumulator_bytes(feats, ("mean", "variance")) == acc
    assert work.accumulator_bytes(feats, ("mean",)) == 4 + 4 * feats
    # 40 selected rows over 8 folded blocks of float32 voxels
    assert work.fold_bytes(40, 8, feats, 4, ("mean", "variance")) == \
        40 * feats * 4 + 8 * acc
    assert work.fold_bytes(0, 0, feats, 4, ("variance",)) == 0


def test_seed_permutes_a_fixed_multiset():
    cell = harness.Cell.find(CELLS[0])
    a = traffic.schedule(cell.mix, cell.rate, 20.0, 1)
    b = traffic.schedule(cell.mix, cell.rate, 20.0, BIG_SEED)
    assert [q.t_due for q in a] == [q.t_due for q in b]
    assert sorted((q.lo, q.hi, q.sex) for q in a) == \
        sorted((q.lo, q.hi, q.sex) for q in b)
    assert [q.lo for q in a] != [q.lo for q in b]
    assert max(q.t_due for q in a) < 20.0
    c1 = cohort.build(cell.config, 1, TINY)
    c2 = cohort.build(cell.config, BIG_SEED, TINY)
    again = cohort.build(cell.config, BIG_SEED, TINY, threads=1)
    np.testing.assert_array_equal(c1.sizes, c2.sizes)
    assert sorted(zip(c1.ages, c1.sexes)) == sorted(zip(c2.ages, c2.sexes))
    np.testing.assert_array_equal(c2.data, again.data)
    assert not np.array_equal(c1.data, c2.data)
    # every query selects the same number of rows whatever the seed
    na = sorted(traffic.mask_of(q, c1.ages, c1.sexes).sum() for q in a)
    nb = sorted(traffic.mask_of(q, c2.ages, c2.sexes).sum() for q in b)
    assert na == nb


def test_reference_matches_numpy():
    rng = np.random.default_rng(0)
    data = rng.standard_normal((30, 3, 4), dtype=np.float32)
    masks = rng.random((3, 30)) < 0.4
    masks[2] = False
    count, mean, var = reference.float64_stats(data, masks, threads=2)
    for k in range(2):
        x = data[masks[k]].astype(np.float64).reshape(masks[k].sum(), -1)
        np.testing.assert_allclose(mean[k], x.mean(0), rtol=1e-12)
        np.testing.assert_allclose(var[k], x.var(0), rtol=1e-9, atol=1e-12)
    assert count[2] == 0 and not mean[2].any() and not var[2].any()


def test_unknown_device_has_no_peaks():
    assert peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peak_for("cpu")


def test_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_catalog_mix_draws_shared_entries_by_zipf():
    mix = harness.load_json(os.path.join(harness.BENCH, "mixes",
                                         "strata_repeat.json"))
    qs = traffic.schedule(mix, 8.0, 50.0, BIG_SEED)
    entries = [q.entry for q in qs]
    counts = np.bincount(entries, minlength=len(mix["filter"]["entries"]))
    assert counts[0] == counts.max() and counts.sum() == len(qs)
    for q in qs:
        assert [q.lo, q.hi, q.sex] == mix["filter"]["entries"][q.entry]
