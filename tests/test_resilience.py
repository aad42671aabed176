"""Resilient-solve suite (DESIGN.md §18): the chaos matrix.

Every recovery path of ``solve(..., resilience=ResilienceConfig(...))``
is exercised with *deterministic* injected faults
(``repro.resilience.chaos``) and must reproduce the fault-free
trajectory to rtol 1e-4 (in fact bit-exactly: snapshots round-trip
fp32 through host memory unchanged):

- transient dispatch failures -> bounded retry from the snapshot ring;
- NaN-poisoned carries -> divergence rollback (ring, then the newest
  *valid* on-disk checkpoint once the ring is dry);
- corrupted newest checkpoint -> resume falls back to the previous
  retention entry (explicit ``resume=step`` stays loud);
- async checkpoint write failures -> surfaced at the next sync point;
- Pallas kernel failures -> per-family compiled->interpret->ref
  degradation with a recorded warning.
"""
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.checkpoint import (Checkpointer, CheckpointCorruptError,
                              CheckpointWriteError, latest_step,
                              latest_valid_step, validate_checkpoint)
from repro.core.problem import solve
from repro.kernels import common as kcommon
from repro.resilience import chaos
from repro.resilience.errors import (DivergenceError, InjectedFault,
                                     ResilienceExhausted, classify)
from repro.resilience.recovery import RecoveryReport, ResilienceConfig

ITERS, CHUNK = 12, 4        # 3 chunk dispatches: first / mid / last


@pytest.fixture(scope="module")
def psf_data():
    from repro.imaging import psf as psf_op
    return psf_op.simulate(8, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def scdl_data():
    from repro.data.synthetic import coupled_patches
    return coupled_patches(256, 25, 9, 16, seed=0)


@pytest.fixture(scope="module")
def lowrank_data():
    rng = np.random.default_rng(4)
    U = rng.normal(size=(24, 3)).astype(np.float32)
    V = rng.normal(size=(3, 18)).astype(np.float32)
    Y = U @ V + 0.01 * rng.normal(size=(24, 18)).astype(np.float32)
    M = (rng.random((24, 18)) < 0.6).astype(np.float32)
    return Y, M


def _solve(workload, data, **kw):
    opts = dict(max_iter=ITERS, tol=0, chunk=CHUNK)
    opts.update(kw)
    if workload == "deconvolve":
        from repro.imaging.condat import SolverConfig
        return solve("deconvolve", data.Y, data.psfs,
                     cfg=SolverConfig(mode="sparse", n_scales=3), **opts)
    if workload == "lowrank":
        from repro.imaging.lowrank import CompletionConfig
        Y, M = data
        return solve("lowrank", Y, M,
                     cfg=CompletionConfig(rank=4, max_iter=ITERS), **opts)
    from repro.imaging.scdl import SCDLConfig
    S_h, S_l = data
    return solve("scdl", S_h, S_l,
                 cfg=SCDLConfig(n_atoms=16, max_iter=ITERS), **opts)


@pytest.fixture(scope="module")
def ref_trajs(psf_data, scdl_data, lowrank_data):
    """Fault-free reference runs, one per workload."""
    return {"deconvolve": _solve("deconvolve", psf_data),
            "scdl": _solve("scdl", scdl_data),
            "lowrank": _solve("lowrank", lowrank_data)}


def _assert_parity(sol, ref):
    np.testing.assert_allclose(sol.log.costs, ref.log.costs, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(sol.x), jax.tree.leaves(ref.x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


# =====================================================================
# The chaos matrix: both workloads x both fault kinds x chunk position
# =====================================================================

@pytest.mark.parametrize("pos", [0, 1, 2], ids=["first", "mid", "last"])
@pytest.mark.parametrize("point", ["dispatch", "carry_nan"])
@pytest.mark.parametrize("workload", ["deconvolve", "scdl", "lowrank"])
def test_chaos_matrix_auto_recovers(workload, point, pos, psf_data,
                                    scdl_data, lowrank_data, ref_trajs):
    data = {"deconvolve": psf_data, "scdl": scdl_data,
            "lowrank": lowrank_data}[workload]
    cc = chaos.ChaosConfig.parse(f"{point}@{pos};seed=11")
    with chaos.active_chaos(cc) as st:
        sol = _solve(workload, data, resilience=ResilienceConfig())
    assert (point, pos) in st.fired
    _assert_parity(sol, ref_trajs[workload])
    rec = sol.recovery
    assert isinstance(rec, RecoveryReport)
    if point == "dispatch":
        assert rec.retries == 1 and rec.rollbacks == 0
        assert rec.faults[0]["point"] == "dispatch"
    else:
        assert rec.rollbacks == 1 and rec.retries == 0
        assert rec.checkpoint_restores == 0
        assert rec.faults[0]["point"] == "divergence"
    assert rec.wall_time_lost_s >= 0.0


def test_fault_free_supervised_run_is_clean(psf_data, ref_trajs):
    sol = _solve("deconvolve", psf_data, resilience=ResilienceConfig())
    _assert_parity(sol, ref_trajs["deconvolve"])
    rec = sol.recovery
    assert rec.retries == rec.rollbacks == rec.checkpoint_restores == 0
    assert rec.faults == [] and rec.kernel_fallbacks == []


def test_unsupervised_run_has_no_recovery(ref_trajs):
    assert ref_trajs["deconvolve"].recovery is None


def test_unsupervised_chaos_fault_is_fatal(psf_data):
    cc = chaos.ChaosConfig.parse("dispatch@1")
    with chaos.active_chaos(cc):
        with pytest.raises(InjectedFault):
            _solve("deconvolve", psf_data)


def test_retry_budget_exhaustion_raises(psf_data):
    cc = chaos.ChaosConfig.parse("dispatch@0,1,2,3,4,5")
    with chaos.active_chaos(cc):
        with pytest.raises(ResilienceExhausted):
            _solve("deconvolve", psf_data,
                   resilience=ResilienceConfig(max_retries=2,
                                               backoff_s=1e-3))


# =====================================================================
# Rollback sources: ring first, then the newest valid disk checkpoint
# =====================================================================

def test_repeated_divergence_falls_back_to_disk(tmp_path, psf_data,
                                                ref_trajs):
    from repro.checkpoint import checkpointer as ckpt
    from repro.core import persistence

    def checkpoint_fn(bundle, i):
        # synchronous write: the disk fallback must find step i+1
        ckpt.save(tmp_path, i + 1, persistence.spill_bundle(bundle))

    # chunk at i=4 diverges twice: rollback #1 consumes the only ring
    # entry, rollback #2 finds the re-pushed snapshot already failed and
    # restores the step-4 checkpoint from disk
    cc = chaos.ChaosConfig.parse("carry_nan@1,2;seed=5")
    with chaos.active_chaos(cc):
        sol = _solve("deconvolve", psf_data,
                     checkpoint_every=CHUNK, checkpoint_fn=checkpoint_fn,
                     resilience=ResilienceConfig(
                         ring=1, checkpoint_dir=str(tmp_path)))
    assert sol.recovery.rollbacks == 2
    assert sol.recovery.checkpoint_restores == 1
    _assert_parity(sol, ref_trajs["deconvolve"])


def test_rollback_budget_exhaustion_raises(psf_data):
    # every chunk invocation poisoned: rollback can never get ahead
    cc = chaos.ChaosConfig.parse(
        "carry_nan@" + ",".join(str(i) for i in range(32)))
    with chaos.active_chaos(cc):
        with pytest.raises(ResilienceExhausted):
            _solve("deconvolve", psf_data,
                   resilience=ResilienceConfig(max_rollbacks=3))


# =====================================================================
# Hardened checkpointing: corruption detection + resume fallback
# =====================================================================

def _corrupt_leaf(directory, step):
    leaf = sorted((Path(directory) / f"step_{step:08d}")
                  .glob("leaf_*.npy"))[0]
    data = leaf.read_bytes()
    leaf.write_bytes(data[: len(data) // 2])


def test_resume_falls_back_past_corrupt_newest(tmp_path, psf_data,
                                               ref_trajs):
    _solve("deconvolve", psf_data, max_iter=8,
           checkpoint_dir=str(tmp_path), checkpoint_every=4)
    assert latest_step(tmp_path) == 8
    assert validate_checkpoint(tmp_path, 8) is None
    _corrupt_leaf(tmp_path, 8)
    assert validate_checkpoint(tmp_path, 8) is not None
    assert latest_valid_step(tmp_path) == (4, [8])

    with pytest.warns(RuntimeWarning, match="integrity"):
        sol = _solve("deconvolve", psf_data,
                     checkpoint_dir=str(tmp_path), resume=True)
    # resumed from step 4 -> iterations 4..11 of the reference run
    assert len(sol.log.costs) == ITERS - 4
    np.testing.assert_allclose(
        sol.log.costs, ref_trajs["deconvolve"].log.costs[4:], rtol=1e-4)


def test_resume_explicit_corrupt_step_stays_loud(tmp_path, psf_data):
    _solve("deconvolve", psf_data, max_iter=8,
           checkpoint_dir=str(tmp_path), checkpoint_every=4)
    _corrupt_leaf(tmp_path, 8)
    with pytest.raises(CheckpointCorruptError, match="integrity"):
        _solve("deconvolve", psf_data,
               checkpoint_dir=str(tmp_path), resume=8)


def test_chaos_ckpt_corrupt_injector(tmp_path, psf_data):
    # the second save (step 8) is torn after its checksums are computed
    cc = chaos.ChaosConfig.parse("ckpt_corrupt@1")
    with chaos.active_chaos(cc):
        _solve("deconvolve", psf_data, max_iter=8,
               checkpoint_dir=str(tmp_path), checkpoint_every=4)
    assert latest_step(tmp_path) == 8
    assert validate_checkpoint(tmp_path, 4) is None
    assert validate_checkpoint(tmp_path, 8) is not None
    assert latest_valid_step(tmp_path) == (4, [8])


# =====================================================================
# Async Checkpointer failure surfacing
# =====================================================================

def test_async_write_failure_surfaces_at_wait(tmp_path):
    tree = {"a": np.arange(8, dtype=np.float32)}
    cc = chaos.ChaosConfig.parse("ckpt_write@0")
    with chaos.active_chaos(cc):
        w = Checkpointer(tmp_path)
        w.save_async(1, tree)
        with pytest.raises(CheckpointWriteError) as ei:
            w.wait()
        assert isinstance(ei.value.__cause__, InjectedFault)
        # the failure is consumed: the next save succeeds and validates
        w.save_async(2, tree)
        w.close()
    assert latest_step(tmp_path) == 2
    assert validate_checkpoint(tmp_path, 2) is None


def test_async_write_failure_surfaces_at_next_save(tmp_path):
    tree = {"a": np.zeros(4, dtype=np.float32)}
    cc = chaos.ChaosConfig.parse("ckpt_write@0")
    with chaos.active_chaos(cc):
        w = Checkpointer(tmp_path)
        w.save_async(1, tree)
        with pytest.raises(CheckpointWriteError):
            w.save(2, tree)
        w.close()


def test_async_write_failure_surfaces_at_close(tmp_path):
    tree = {"a": np.zeros(4, dtype=np.float32)}
    cc = chaos.ChaosConfig.parse("ckpt_write@0")
    with chaos.active_chaos(cc):
        w = Checkpointer(tmp_path)
        w.save_async(1, tree)
        with pytest.raises(CheckpointWriteError):
            w.close()


def test_solve_surfaces_async_checkpoint_failure(tmp_path, psf_data):
    cc = chaos.ChaosConfig.parse("ckpt_write@0")
    with chaos.active_chaos(cc):
        with pytest.raises(CheckpointWriteError):
            _solve("deconvolve", psf_data, max_iter=8,
                   checkpoint_dir=str(tmp_path), checkpoint_every=4)


# =====================================================================
# Kernel degradation: compiled -> interpret -> ref, once per family
# =====================================================================

@pytest.fixture
def fresh_kernels():
    kcommon.reset_degradation()
    yield
    kcommon.reset_degradation()


def test_kernel_degradation_parity_and_warning(fresh_kernels):
    from repro.kernels.dict_outer.ops import dict_outer
    from repro.kernels.dict_outer.ref import dict_outer_ref
    rng = np.random.default_rng(0)
    S = np.asarray(rng.normal(size=(64, 16)), np.float32)
    W = np.asarray(rng.normal(size=(64, 16)), np.float32)
    cc = chaos.ChaosConfig.parse("kernel:dict_outer@0;seed=3")
    with chaos.active_chaos(cc):
        with pytest.warns(RuntimeWarning, match="degraded"):
            got = dict_outer(S, W, use_kernel=True)
    want = dict_outer_ref(S, W)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)
    events = kcommon.kernel_fallbacks()
    assert [e["family"] for e in events] == ["dict_outer"]
    # degradation is per-family and sticky: the next call silently uses
    # the surviving level, no new event, no new warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        again = dict_outer(S, W, use_kernel=True)
    np.testing.assert_allclose(np.asarray(again[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)
    assert len(kcommon.kernel_fallbacks()) == 1


def test_kernel_degradation_reset(fresh_kernels):
    from repro.kernels.condat_elwise.ops import condat_dual
    from repro.kernels.condat_elwise.ref import condat_dual_ref
    rng = np.random.default_rng(1)
    U = np.asarray(rng.normal(size=(2, 4, 8, 8)), np.float32)
    C = np.asarray(rng.normal(size=(2, 4, 8, 8)), np.float32)
    W = np.asarray(rng.normal(size=(2, 4, 1, 1)), np.float32) ** 2
    cc = chaos.ChaosConfig.parse("kernel:condat_elwise@0")
    with chaos.active_chaos(cc):
        with pytest.warns(RuntimeWarning, match="condat_elwise"):
            got = condat_dual(U, C, 0.9 * C, W, 0.5, use_kernel=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(condat_dual_ref(U, C, 0.9 * C, W, 0.5)),
        rtol=1e-5, atol=1e-5)
    kcommon.reset_degradation()
    assert kcommon.kernel_fallbacks() == ()
    # healthy again after reset: no warning on the next call
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        condat_dual(U, C, 0.9 * C, W, 0.5, use_kernel=True)


def _broken_kernel(interpret):
    raise RuntimeError("Mosaic refused the kernel")


def test_kernel_failure_degrades_on_cpu_backend(fresh_kernels):
    with pytest.warns(RuntimeWarning, match="degraded"):
        out = kcommon.degraded_call("starlet2d", kernel=_broken_kernel,
                                    ref=lambda: "ref",
                                    requested_interpret=False)
    assert out == "ref"
    assert [e["to"] for e in kcommon.kernel_fallbacks()] == \
        ["interpret", "ref"]


def test_kernel_failure_raises_on_tpu_backend(fresh_kernels, monkeypatch):
    """On the chip a kernel Mosaic refuses is a bug to fix: no silent
    fallback to the interpreter, at either level, and no event.  An
    injected chaos fault still degrades, so the drills keep working."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for interpret in (False, True):
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            kcommon.degraded_call("starlet2d", kernel=_broken_kernel,
                                  ref=lambda: "ref",
                                  requested_interpret=interpret)
    assert kcommon.kernel_fallbacks() == ()
    cc = chaos.ChaosConfig.parse("kernel:starlet2d@0")
    with chaos.active_chaos(cc):
        with pytest.warns(RuntimeWarning, match="degraded"):
            out = kcommon.degraded_call(
                "starlet2d", kernel=lambda interp: ("kernel", interp),
                ref=lambda: "ref", requested_interpret=False)
    assert out == ("kernel", True)
    assert [e["to"] for e in kcommon.kernel_fallbacks()] == ["interpret"]


def test_reference_kernels_scope(fresh_kernels):
    calls = []

    def kernel(interp):
        calls.append(interp)
        return "kernel"

    with kcommon.reference_kernels():
        assert kcommon.degraded_call("dict_outer", kernel=kernel,
                                     ref=lambda: "ref") == "ref"
    assert calls == []
    assert kcommon.degraded_call("dict_outer", kernel=kernel,
                                 ref=lambda: "ref",
                                 requested_interpret=True) == "kernel"
    assert kcommon.kernel_fallbacks() == ()


def test_solve_reports_kernel_fallbacks(fresh_kernels, psf_data,
                                        ref_trajs):
    # the deconvolution step traces the starlet kernels: an injected
    # construction fault degrades the family and lands on the report
    cc = chaos.ChaosConfig.parse("kernel:starlet2d@0")
    with chaos.active_chaos(cc):
        with pytest.warns(RuntimeWarning, match="starlet2d"):
            sol = _solve("deconvolve", psf_data,
                         resilience=ResilienceConfig())
    assert any(e["family"] == "starlet2d"
               for e in sol.recovery.kernel_fallbacks)
    # ref-path parity: the degraded run still reproduces the trajectory
    np.testing.assert_allclose(sol.log.costs,
                               ref_trajs["deconvolve"].log.costs,
                               rtol=1e-4)


# =====================================================================
# Chaos plumbing + error taxonomy
# =====================================================================

def test_chaos_spec_parsing():
    cc = chaos.ChaosConfig.parse("dispatch@1,3;carry_nan;seed=9")
    assert cc.seed == 9
    assert cc.faults == {"dispatch": (1, 3), "carry_nan": (0,)}
    with pytest.raises(ValueError, match="unknown chaos fault point"):
        chaos.ChaosConfig.parse("warp_core@0")


def test_chaos_env_var_path(monkeypatch, psf_data):
    monkeypatch.setenv(chaos.ENV_VAR, "dispatch@1;seed=3")
    assert not chaos.is_active()
    sol = _solve("deconvolve", psf_data, resilience=ResilienceConfig())
    assert sol.recovery.retries == 1
    assert sol.recovery.faults[0]["point"] == "dispatch"
    assert not chaos.is_active()        # deactivated after the run


def test_classify_taxonomy():
    assert classify(InjectedFault("dispatch")) == "transient"
    assert classify(OSError("disk gone")) == "transient"
    assert classify(RuntimeError("UNAVAILABLE: worker lost")) \
        == "transient"
    assert classify(ValueError("bad shape")) == "fatal"
    assert classify(DivergenceError("nan", step=3)) == "fatal"
    assert classify(ResilienceExhausted("done")) == "fatal"
    class Custom(Exception):
        pass
    assert classify(Custom(), (Custom,)) == "transient"


def test_recovery_report_json_schema():
    rep = RecoveryReport()
    rep.retries = 2
    rep.record_fault("dispatch", 8, InjectedFault("dispatch", step=8))
    out = rep.to_json()
    assert set(out) == {"retries", "rollbacks", "checkpoint_restores",
                        "faults", "kernel_fallbacks", "wall_time_lost_s"}
    assert out["retries"] == 2
    assert out["faults"][0]["point"] == "dispatch"
    assert out["faults"][0]["step"] == 8
    assert "retries=2" in str(rep)


def test_resilience_config_requires_ring():
    with pytest.raises(ValueError, match="ring"):
        ResilienceConfig(ring=0)
