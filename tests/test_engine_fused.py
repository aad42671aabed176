"""Fused-iteration engine tests: `make_scan_step(chunk=K)` trajectories
must match the per-step driver and the sequential reference for every
workload, including chunk lengths that don't divide max_iter (tail
chunks) and cost_every skipping."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bundle import Bundle
from repro.core.driver import IterativeDriver, RunOptions
from repro.core.engine import make_scan_step
from repro.core.problem import solve as solve_problem
from repro.data.synthetic import coupled_patches
from repro.imaging import psf as psf_op
from repro.imaging.condat import SolverConfig, solve
from repro.imaging.deconvolve import DeconvolutionProblem
from repro.imaging.scdl import SCDLConfig, SCDLProblem


def deconvolve(Y, psfs, cfg, sigma_noise=0.02, **kw):
    """Drive Algorithm 1 through solve() (the shim-free path; the
    deprecated legacy signatures are covered by test_problem_api)."""
    sol = solve_problem(DeconvolutionProblem(cfg, sigma_noise=sigma_noise),
                        Y, psfs, **kw)
    return sol.x, sol.log


def train(S_h, S_l, cfg, **kw):
    """Drive Algorithm 2 through solve()."""
    sol = solve_problem(SCDLProblem(cfg), S_h, S_l, **kw)
    Xh, Xl = sol.x
    return Xh, Xl, sol.log


KEY = jax.random.PRNGKey(2)
N_ITER = 12


@pytest.fixture(scope="module")
def psf_data():
    return psf_op.simulate(8, KEY)


@pytest.mark.parametrize("mode", ["sparse", "lowrank"])
@pytest.mark.parametrize("chunk", [4, 5, 32])
def test_fused_matches_per_step_and_sequential(psf_data, mode, chunk):
    """chunk=5 exercises the tail chunk (12 = 5 + 5 + 2); chunk=32 a
    single chunk longer than the run."""
    cfg = SolverConfig(mode=mode, n_scales=3, lam=0.05, rank=8)
    _, costs_seq = solve(psf_data.Y, psf_data.psfs, cfg,
                         sigma_noise=psf_data.sigma, n_iter=N_ITER)
    _, log_1 = deconvolve(psf_data.Y, psf_data.psfs, cfg,
                          sigma_noise=psf_data.sigma, max_iter=N_ITER,
                          tol=0, chunk=1)
    _, log_k = deconvolve(psf_data.Y, psf_data.psfs, cfg,
                          sigma_noise=psf_data.sigma, max_iter=N_ITER,
                          tol=0, chunk=chunk)
    assert len(log_k.costs) == N_ITER
    # low-rank replaces the reference's exact SVT with the randomized
    # range-finder SVT (DESIGN.md §2) — match the reference loosely and
    # the per-step driver (same math) tightly
    seq_rtol = 1e-5 if mode == "sparse" else 5e-2
    np.testing.assert_allclose(np.asarray(log_1.costs),
                               np.asarray(costs_seq), rtol=seq_rtol)
    np.testing.assert_allclose(np.asarray(log_k.costs),
                               np.asarray(log_1.costs), rtol=1e-5)


def test_fused_cost_every_matches_on_grid(psf_data):
    cfg = SolverConfig(mode="sparse", n_scales=3)
    X1, log_1 = deconvolve(psf_data.Y, psf_data.psfs, cfg,
                           sigma_noise=psf_data.sigma, max_iter=N_ITER,
                           tol=0, chunk=4, cost_every=1)
    X3, log_3 = deconvolve(psf_data.Y, psf_data.psfs, cfg,
                           sigma_noise=psf_data.sigma, max_iter=N_ITER,
                           tol=0, chunk=4, cost_every=3)
    # identical iterates; objective evaluated only on the cost grid
    np.testing.assert_allclose(X3, X1, rtol=1e-6, atol=1e-7)
    c1, c3 = np.asarray(log_1.costs), np.asarray(log_3.costs)
    np.testing.assert_allclose(c3[::3], c1[::3], rtol=1e-5)
    # off-grid entries carry the last evaluated cost forward
    assert c3[1] == c3[0] and c3[2] == c3[0]
    # ...including across a chunk boundary (i=4 starts chunk 2 with
    # 4 % 3 != 0): the carry must survive the dispatch, not reset to 0
    assert c3[4] == c3[3] and c3[5] == c3[3]
    assert (c3 != 0.0).all()


def test_per_step_cost_every_matches_on_grid(psf_data):
    """cost_every must also skip on the chunk=1 (per-step) path."""
    cfg = SolverConfig(mode="sparse", n_scales=3)
    X1, log_1 = deconvolve(psf_data.Y, psf_data.psfs, cfg,
                           sigma_noise=psf_data.sigma, max_iter=6,
                           tol=0, chunk=1, cost_every=1)
    X3, log_3 = deconvolve(psf_data.Y, psf_data.psfs, cfg,
                           sigma_noise=psf_data.sigma, max_iter=6,
                           tol=0, chunk=1, cost_every=3)
    np.testing.assert_allclose(X3, X1, rtol=1e-6, atol=1e-7)
    c1, c3 = np.asarray(log_1.costs), np.asarray(log_3.costs)
    np.testing.assert_allclose(c3[::3], c1[::3], rtol=1e-5)
    assert c3[1] == c3[0] and c3[4] == c3[3]


@pytest.mark.parametrize("chunk", [4, 5])
def test_scdl_fused_matches_per_step(chunk):
    S_h, S_l = coupled_patches(256, 25, 9, 16, seed=5)
    cfg = SCDLConfig(n_atoms=16, max_iter=N_ITER)
    Xh1, Xl1, log_1 = train(S_h, S_l, cfg, chunk=1)
    Xhk, Xlk, log_k = train(S_h, S_l, cfg, chunk=chunk)
    assert len(log_k.costs) == N_ITER
    np.testing.assert_allclose(log_k.costs, log_1.costs, rtol=1e-5)
    # chunk=1 folds the broadcast factors on the host (eager) vs in the
    # scan carry (jitted) — identical algebra, ulp-level fp differences
    np.testing.assert_allclose(Xhk, Xh1, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(Xlk, Xl1, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunk", [1, 4])
def test_scdl_cost_every_matches_on_grid(chunk):
    """SCDL's cost_every (the light step feeds the dictionary broadcast
    every iteration — ``light_updates_replicated``): the same iterates,
    objective only on the grid, on both the fused and per-step paths.

    The fused chunk with ``cost_every=3`` is a different XLA program
    (a ``cond`` between full and light steps inside the scan), which
    XLA:CPU fuses and rounds differently.  This trajectory amplifies
    rounding about 10^3-fold — a 1-ulp perturbation of S_h moves Xh by
    up to 4e-5 — so the fused iterates agree to that scale (measured
    8e-6); the per-step path runs the same step programs either way and
    stays within fp32 epsilon."""
    S_h, S_l = coupled_patches(256, 25, 9, 16, seed=5)
    cfg = SCDLConfig(n_atoms=16, max_iter=N_ITER)
    Xh1, _, log_1 = train(S_h, S_l, cfg, chunk=chunk, cost_every=1)
    Xh3, _, log_3 = train(S_h, S_l, cfg, chunk=chunk, cost_every=3)
    np.testing.assert_allclose(Xh3, Xh1, rtol=1e-5,
                               atol=1e-7 if chunk == 1 else 4e-5)
    c1, c3 = np.asarray(log_1.costs), np.asarray(log_3.costs)
    np.testing.assert_allclose(c3[::3], c1[::3], rtol=1e-5)
    # off-grid entries carry the last evaluated objective forward,
    # including across the chunk boundary at i=4 (4 % 3 != 0)
    assert c3[1] == c3[0] and c3[2] == c3[0]
    assert c3[4] == c3[3] and c3[5] == c3[3]


def test_scdl_per_chunk_cost_matches(chunk=5):
    """cost_every="chunk" (engine.make_chunk_cost_step): no cond in the
    scan body, one objective evaluation per dispatch on the chunk-final
    state — entries match the full run at chunk-final iterations, the
    rest carry the previous evaluation (+inf before the first)."""
    S_h, S_l = coupled_patches(256, 25, 9, 16, seed=5)
    cfg = SCDLConfig(n_atoms=16, max_iter=N_ITER)
    Xh1, _, log_1 = train(S_h, S_l, cfg, chunk=chunk)
    Xhc, _, log_c = train(S_h, S_l, cfg, chunk=chunk,
                          cost_every="chunk")
    np.testing.assert_allclose(Xhc, Xh1, rtol=1e-5, atol=1e-7)
    c1, cc = np.asarray(log_1.costs), np.asarray(log_c.costs)
    assert len(cc) == N_ITER
    # chunk-final entries: 4, 9, and the tail chunk's 11 (12 = 5+5+2)
    for i in (4, 9, 11):
        np.testing.assert_allclose(cc[i], c1[i], rtol=1e-5)
    assert np.isinf(cc[0]) and cc[5] == cc[4]


def test_make_scan_step_cost_buffer_and_carry():
    """Direct engine-level check: (K,) cost buffer, replicated carried
    through the scan via update_replicated."""
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (64, 4))
    y = X @ jnp.arange(1.0, 5.0)
    bundle = Bundle.create({"X": X, "y": y},
                           replicated={"w": jnp.zeros((4,))})

    def step(d, rep, axes):
        r = d["X"] @ rep["w"] - d["y"]
        grad = d["X"].T @ r / d["X"].shape[0]
        cost = 0.5 * jnp.sum(r ** 2)
        if axes:
            grad = jax.lax.psum(grad, axes)
            cost = jax.lax.psum(cost, axes)
        return d, {"cost": cost, "w": rep["w"] - 0.1 * grad}

    fused = make_scan_step(step, bundle, chunk=6, donate=False,
                           update_replicated=lambda rep, out:
                           {"w": out["w"]})
    data, rep, trace = fused(bundle.data, bundle.replicated, 0)
    assert trace["cost"].shape == (6,)
    # dictionaries/matrix outputs are folded into the carry, not stacked
    assert "w" not in trace
    costs = np.asarray(trace["cost"])
    assert (np.diff(costs) < 0).all()          # GD on a ridge problem

    # the fused trajectory equals six per-step applications
    rep_ref = {"w": jnp.zeros((4,))}
    ref_costs = []
    d_ref = bundle.data
    for _ in range(6):
        d_ref, out = step(d_ref, rep_ref, ())
        ref_costs.append(float(out["cost"]))
        rep_ref = {"w": out["w"]}
    np.testing.assert_allclose(costs, ref_costs, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rep["w"]),
                               np.asarray(rep_ref["w"]), rtol=1e-6)


def test_driver_chunked_convergence_and_log():
    """Chunked driver stops on the chunk boundary after convergence and
    logs per-iteration times."""
    key = jax.random.PRNGKey(1)
    X = jax.random.normal(key, (32, 3))
    y = X @ jnp.ones((3,))
    bundle = Bundle.create({"X": X, "y": y},
                           replicated={"w": jnp.zeros((3,))})

    def step(d, rep, axes):
        r = d["X"] @ rep["w"] - d["y"]
        grad = d["X"].T @ r / d["X"].shape[0]
        return d, {"cost": 0.5 * jnp.sum(r ** 2),
                   "w": rep["w"] - 0.3 * grad}

    driver = IterativeDriver(
        step, bundle, options=RunOptions(
            max_iter=200, tol=1e-6, chunk=8,
            update_replicated=lambda rep, out: {"w": out["w"]}))
    out = driver.run()
    assert driver.log.converged_at is not None
    assert (driver.log.converged_at + 1) % 8 == 0
    assert len(driver.log.times) == len(driver.log.costs)
    w = np.asarray(out.replicated["w"])
    np.testing.assert_allclose(w, np.ones(3), rtol=1e-2)
