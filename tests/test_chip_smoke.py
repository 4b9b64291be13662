"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny volume size.

The script proper insists on a TPU; its phases are plain functions, so here
they run on the CPU with the fold kernel in interpret mode
(``fold_interpret=True``) over the same cohort recipe (226 subjects, 8
regions) with 4x5x6 volumes.  The four-device mesh phase runs in a child
process with four forced host devices, since this process keeps one.
"""

import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = (4, 5, 6)


@pytest.fixture(scope="module")
def cohort():
    return chip_smoke.build_cohort(payload_shape=TINY)


def test_cohort_recipe(cohort):
    assert cohort.num_rows == 226
    assert len(cohort.regions) == 8
    rows = sorted(cohort.region_row_counts().values())
    assert rows[0] >= 17 and rows[-1] <= 32     # every block buckets to 32


def test_reference_matches_numpy(cohort):
    ref = chip_smoke.CohortReference.of_table(cohort)
    data = cohort.column("img", "data").astype(np.float64)
    sex = cohort.column("idx", "sex")
    age = cohort.column("idx", "age")
    lo, hi, s = chip_smoke.SUBSET
    for name, sel in (("all", np.ones(len(sex), bool)),
                      ("sex0", sex == 0), ("sex1", sex == 1),
                      ("subset", (age >= lo) & (age < hi) & (sex == s))):
        n, mean, var = ref.stats(name)
        assert n == sel.sum()
        np.testing.assert_allclose(mean, data[sel].mean(0), atol=1e-12)
        np.testing.assert_allclose(var, data[sel].var(0), atol=1e-10)


def test_single_chip_phase(cohort):
    ref = chip_smoke.CohortReference.of_table(cohort)
    obs = chip_smoke.single_chip_phase(cohort, ref, jax.devices()[:1],
                                       fold_interpret=True)
    assert obs["fold_path_counts"]["xla"] == 0
    assert obs["fold_path_counts"]["pallas"] > 0
    assert obs["q4_repeat_rows_folded"] == 0
    assert obs["q5_regions_refolded"] == 1
    assert obs["rows_per_block"] == [32] * 8
    assert cohort.num_rows == 226 + chip_smoke.UPLOAD_ROWS


def test_check_answer_rejects_a_wrong_mean():
    t = chip_smoke.build_cohort(payload_shape=(2, 2))
    ref = chip_smoke.CohortReference.of_table(t)
    n, mean, var = ref.stats("all")
    good = {"var": var, "count": np.float32(n)}
    chip_smoke.check_answer("exact", mean, good, ref, "all")
    with pytest.raises(AssertionError):
        chip_smoke.check_answer("off", mean + 1e-3, good, ref, "all")


def _child(args, env_extra=None, cwd=REPO):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=600)


def test_mesh_phase_on_four_host_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax, chip_smoke
        assert jax.device_count() == 4
        t = chip_smoke.build_cohort(payload_shape={TINY!r})
        ref = chip_smoke.CohortReference.of_table(t)
        obs = chip_smoke.mesh_phase(t, ref, jax.devices()[:4],
                                    fold_interpret=True)
        assert obs["regions_moved"] > 0
        print("MESH_OK", obs["tree_vs_funnel_max_diff"])
    """)
    proc = _child(["-c", code], {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "MESH_OK" in proc.stdout
    assert "merge_path=tree" in proc.stdout
    assert "merge_path=funnel" in proc.stdout


def _assert_no_result(proc):
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_main_refuses_a_cpu_backend():
    proc = _child([os.path.join(REPO, "chip_smoke.py")])
    _assert_no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _child([str(tmp_path / "chip_smoke.py")],
                  {"PYTHONPATH": ""}, cwd=str(tmp_path))
    _assert_no_result(proc)
