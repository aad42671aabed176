"""The check that decides ``correct``: the control fails it, and so does
the harness driven through a whole CPU rehearsal with the timed path
broken underneath; the harness refuses to measure without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import control  # noqa: E402
import run  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
SPARSE, LOWRANK = "deconv_survey.sparse", "deconv_survey.lowrank"
STAMPS = 24


def limit(cell, name):
    return cells.resolve(BENCH, cell).traffic["limits"][name]


def rehearse(cell, seed, records, seconds=0.5):
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--rehearse", str(records)])


# ------------------------------------------------------------ control

@pytest.mark.parametrize("cell,stamps,iters", [(SPARSE, STAMPS, 24),
                                               (LOWRANK, 128, 200)])
def test_deconvolution_control_fails_the_limit(cell, stamps, iters):
    line, = control.main(["--workload", cell, "--seeds", "5", "--iters",
                          str(iters), "--rehearse", str(stamps)])
    assert line["control"]["x_gap"] > limit(cell, "x_gap")


# -------------------------------------- whole runs, timed path broken

@pytest.mark.parametrize("cell", [SPARSE, LOWRANK])
def test_sound_rehearsal_is_correct(cell):
    res = rehearse(cell, 2 ** 32 + 3, STAMPS)
    assert res["correct"], res["checks"]
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


def _frozen(orig):
    """The step's work is done, and its state returned unchanged."""
    def step(self, d, rep, axes):
        _, out = orig(self, d, rep, axes)
        return d, out
    return step


def _half_batch(orig):
    def step(self, d, rep, axes):
        new, out = orig(self, d, rep, axes)
        n = d["Xp"].shape[0]
        keep = jnp.arange(n) < n // 2
        new = jax.tree.map(
            lambda a, b: jnp.where(keep.reshape((n,) + (1,) * (a.ndim - 1)),
                                   a, b), new, d)
        return new, out
    return step


@pytest.mark.parametrize("cell", [SPARSE, LOWRANK])
def test_deconvolution_faults_make_the_run_incorrect(monkeypatch, cell):
    from repro.imaging import deconvolve
    cls = deconvolve.DeconvolutionProblem
    seed = 17
    with monkeypatch.context() as m:
        m.setattr(cls, "full_step", _frozen(cls.full_step))
        assert not rehearse(cell, seed, STAMPS)["correct"]
    with monkeypatch.context() as m:
        m.setattr(cls, "full_step", _half_batch(cls.full_step))
        assert not rehearse(cell, seed, STAMPS)["correct"]

    # one compared stamp altered where the answer is produced
    ref = cells.load_module(HERE / "reference" / "deconvolve.py",
                            "ref_deconv")
    victim = int(ref.sample(cells.resolve(BENCH, cell, STAMPS), seed)[0])
    orig = cls.finalize

    def altered(self, bundle, log):
        x, aux = orig(self, bundle, log)
        x = np.array(x)
        x[victim, 20, 20] += 0.05 * np.max(x[victim])
        return x, aux

    with monkeypatch.context() as m:
        m.setattr(cls, "finalize", altered)
        assert not rehearse(cell, seed, STAMPS)["correct"]


def test_a_metric_the_driver_does_not_report_is_refused(monkeypatch):
    solve = cells.component("drivers", "solve")
    orig = solve.drive

    def silent(r):
        driven = orig(r)
        return dict(driven, values={})

    monkeypatch.setattr(solve, "drive", silent)
    with pytest.raises(SystemExit):
        rehearse(SPARSE, 31, STAMPS)


def test_driver_reports_the_window_it_measured():
    res = rehearse(SPARSE, 37, STAMPS, seconds=0.3)
    assert set(res["metrics"]) == {"iter_ms", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["iter_ms"]["value"] > 0


# ----------------------------------------------------------- refusals

def _cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_means_no_result():
    out = _cli(["--workload", SPARSE, "--seed", "1", "--seconds", "1",
                "--trace", "0"], ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(["--workload", SPARSE, "--seed", "1", "--seconds", "1",
                "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()


def test_reference_compares_by_the_widest_gap():
    ref = cells.component("reference", "deconvolve")
    r = np.ones((3, 4, 4))
    x = r.copy()
    x[1, 2, 2] += 0.25
    assert ref.compare(x, r)["x_gap"] == pytest.approx(0.25)
    x[2, 0, 0] = np.nan
    assert ref.compare(x, r)["x_gap"] == float("inf")
