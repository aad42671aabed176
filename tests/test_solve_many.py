"""solve_many: pad-and-bucket batched multi-instance solve (§19).

The contract under test: for every builtin workload, each instance of a
batched run reproduces its own single ``solve()`` trajectory (cost curve
to rtol 1e-4, iterate to fp noise), while converged instances are frozen
in place by the active mask (fewer ``iters_run`` than the bucket's
running maximum) and the whole thing composes with checkpointing and
supervised execution.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.driver import RunOptions
from repro.core.problem import Solution, solve, solve_many
from repro.resilience import chaos
from repro.resilience.recovery import ResilienceConfig

ITERS, CHUNK = 10, 4


@pytest.fixture(scope="module")
def psf_instances():
    from repro.imaging import psf as psf_op
    out = []
    for (n, S, seed) in [(3, 16, 0), (5, 16, 1), (4, 16, 2), (3, 20, 3)]:
        d = psf_op.simulate(n, jax.random.PRNGKey(seed), stamp=S)
        out.append((d.Y, d.psfs))
    return out


def _deconv_cfg(**kw):
    from repro.imaging.condat import SolverConfig
    base = dict(mode="sparse", max_iter=ITERS, tol=0.0, n_scales=2)
    base.update(kw)
    return SolverConfig(**base)


def _assert_instance_parity(sol, ref, rtol=1e-4, atol=1e-6):
    fin = np.isfinite(np.asarray(ref.log.costs))
    np.testing.assert_allclose(np.asarray(sol.log.costs)[fin],
                               np.asarray(ref.log.costs)[fin], rtol=rtol)
    for a, b in zip(jax.tree.leaves(sol.x), jax.tree.leaves(ref.x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


# =====================================================================
# Per-instance trajectory parity, all three workloads
# =====================================================================

@pytest.mark.parametrize("cost_every", [1, 3, "chunk"])
def test_deconvolve_parity_all_cadences(psf_instances, cost_every):
    cfg = _deconv_cfg()
    sols = solve_many("deconvolve", psf_instances, cfg=cfg,
                      chunk=CHUNK, cost_every=cost_every)
    assert all(isinstance(s, Solution) for s in sols)
    for inst, sol in zip(psf_instances, sols):
        ref = solve("deconvolve", *inst, cfg=cfg,
                    chunk=CHUNK, cost_every=cost_every)
        assert sol.x.shape == inst[0].shape     # unpadded result
        assert sol.log.iters_run == ITERS
        _assert_instance_parity(sol, ref)


def test_lowrank_parity():
    from repro.imaging.lowrank import CompletionConfig

    def make(n, p, seed):
        r = np.random.default_rng(seed)
        Y = (r.normal(size=(n, 3)) @ r.normal(size=(3, p))).astype(
            np.float32)
        M = (r.random((n, p)) < 0.6).astype(np.float32)
        return jnp.asarray(Y), jnp.asarray(M)

    # The iterate is an SVT output: its singular vectors carry rounding
    # of order eps * sigma_1 / gap, which the batched program (another
    # XLA program) rounds differently.  A 1-ulp perturbation of Y moves
    # the single-solve iterate by up to 2.5e-6 of its peak, so iterates
    # agree to atol 1e-5; the objective keeps rtol 1e-4.
    insts = [make(8, 10, 0), make(6, 10, 1), make(8, 12, 2)]
    cfg = CompletionConfig(rank=4, max_iter=ITERS, tol=0.0)
    sols = solve_many("lowrank", insts, cfg=cfg, chunk=CHUNK)
    for inst, sol in zip(insts, sols):
        _assert_instance_parity(
            sol, solve("lowrank", *inst, cfg=cfg, chunk=CHUNK), atol=1e-5)


def test_scdl_parity():
    from repro.imaging.scdl import SCDLConfig

    def make(K, seed):
        r = np.random.default_rng(seed)
        return (jnp.asarray(r.normal(size=(25, K)).astype(np.float32)),
                jnp.asarray(r.normal(size=(16, K)).astype(np.float32)))

    insts = [make(20, 0), make(20, 1), make(24, 2)]
    cfg = SCDLConfig(n_atoms=6, max_iter=ITERS, tol=0.0)
    sols = solve_many("scdl", insts, cfg=cfg, chunk=CHUNK)
    for inst, sol in zip(insts, sols):
        _assert_instance_parity(
            sol, solve("scdl", *inst, cfg=cfg, chunk=CHUNK))


# =====================================================================
# Masked early exit
# =====================================================================

def test_masked_early_exit_frees_converged_instance():
    from repro.imaging import psf as psf_op
    d = psf_op.simulate(4, jax.random.PRNGKey(9), stamp=16)
    live = (d.Y, d.psfs)
    settled = (jnp.zeros_like(d.Y), d.psfs)   # converges immediately
    cfg = _deconv_cfg(max_iter=40, tol=1e-6)
    sols = solve_many("deconvolve", [live, settled], cfg=cfg,
                      chunk=CHUNK, cost_every=1)
    assert sols[1].log.iters_run < sols[0].log.iters_run
    assert sols[1].log.converged_at is not None
    assert sols[1].log.converged_at + 1 == sols[1].log.iters_run
    # the frozen lane's iterate is exactly its state at convergence:
    # still the zero image the zero observations fix
    np.testing.assert_array_equal(np.asarray(sols[1].x), 0.0)
    # and the live lane is untouched by sharing a bucket with it
    # (single solve does not track iters_run; its cost log is one entry
    # per iteration actually run)
    ref = solve("deconvolve", *live, cfg=cfg, chunk=CHUNK, cost_every=1)
    assert sols[0].log.iters_run == len(ref.log.costs)
    _assert_instance_parity(sols[0], ref)


# =====================================================================
# Checkpoint / resume / resilience composition
# =====================================================================

def test_bucket_checkpoint_resume_roundtrip(tmp_path, psf_instances):
    cfg = _deconv_cfg()
    ref = solve_many("deconvolve", psf_instances, cfg=cfg,
                     chunk=CHUNK, cost_every=1)
    solve_many("deconvolve", psf_instances, cfg=_deconv_cfg(max_iter=8),
               chunk=CHUNK, cost_every=1,
               checkpoint_dir=str(tmp_path), checkpoint_every=4)
    assert all(d.startswith("bucket_") for d in os.listdir(tmp_path))
    assert len(os.listdir(tmp_path)) >= 2      # mixed shapes: 2+ buckets
    res = solve_many("deconvolve", psf_instances, cfg=cfg,
                     chunk=CHUNK, cost_every=1,
                     checkpoint_dir=str(tmp_path), resume=True)
    for r, s in zip(ref, res):
        np.testing.assert_array_equal(np.asarray(r.x), np.asarray(s.x))
        assert s.log.iters_run == ITERS


def test_resume_requires_true_not_step(tmp_path, psf_instances):
    with pytest.raises(ValueError, match="resume=True"):
        solve_many("deconvolve", psf_instances, cfg=_deconv_cfg(),
                   checkpoint_dir=str(tmp_path), resume=4,
                   checkpoint_every=4)


def test_resume_without_any_bucket_checkpoints(tmp_path, psf_instances):
    with pytest.raises(ValueError, match="no bucket checkpoints"):
        solve_many("deconvolve", psf_instances, cfg=_deconv_cfg(),
                   checkpoint_dir=str(tmp_path), resume=True)


def test_chaos_drill_on_batched_run(tmp_path, psf_instances):
    cfg = _deconv_cfg()
    ref = solve_many("deconvolve", psf_instances, cfg=cfg,
                     chunk=CHUNK, cost_every=1)
    cc = chaos.ChaosConfig.parse("dispatch@1;carry_nan@2;seed=7")
    with chaos.active_chaos(cc) as st:
        sols = solve_many("deconvolve", psf_instances, cfg=cfg,
                          chunk=CHUNK, cost_every=1,
                          checkpoint_dir=str(tmp_path),
                          checkpoint_every=4,
                          resilience=ResilienceConfig(backoff_s=1e-3))
    assert ("dispatch", 1) in st.fired and ("carry_nan", 2) in st.fired
    hit = [s.recovery for s in sols
           if s.recovery.retries or s.recovery.rollbacks]
    assert hit, "injected faults landed on no bucket"
    for r, s in zip(ref, sols):
        _assert_instance_parity(s, r)


# =====================================================================
# Option validation (satellite: RunOptions hardening)
# =====================================================================

@pytest.mark.parametrize("bad", [0, -1, -8])
def test_run_options_rejects_nonpositive_chunk(bad):
    with pytest.raises(ValueError, match="chunk"):
        RunOptions(max_iter=4, chunk=bad)


@pytest.mark.parametrize("bad", [0, -3])
def test_run_options_rejects_nonpositive_cost_every(bad):
    with pytest.raises(ValueError, match="cost_every"):
        RunOptions(max_iter=4, cost_every=bad)


def test_run_options_rejects_unknown_cost_every_string():
    with pytest.raises(ValueError, match="chunk"):
        RunOptions(max_iter=4, cost_every="sometimes")


def test_checkpoint_every_clamped_to_max_iter(tmp_path, psf_instances):
    # checkpoint_every far beyond max_iter still writes the final step,
    # mirroring the chunk clamp
    solve_many("deconvolve", psf_instances[:1], cfg=_deconv_cfg(),
               chunk=CHUNK, checkpoint_dir=str(tmp_path),
               checkpoint_every=10_000)
    from repro.checkpoint import latest_step
    bdirs = os.listdir(tmp_path)
    assert len(bdirs) == 1
    assert latest_step(tmp_path / bdirs[0]) == ITERS


# =====================================================================
# Misc contracts
# =====================================================================

def test_empty_instance_list():
    assert solve_many("deconvolve", [], cfg=_deconv_cfg()) == []


def test_single_instance_bucket(psf_instances):
    cfg = _deconv_cfg()
    [sol] = solve_many("deconvolve", psf_instances[:1], cfg=cfg,
                       chunk=CHUNK)
    _assert_instance_parity(
        sol, solve("deconvolve", *psf_instances[0], cfg=cfg, chunk=CHUNK))
