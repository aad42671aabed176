"""Use-case tests: starlet/PSF operator properties (hypothesis) and the
distributed == sequential equivalences of Algorithms 1 & 2."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.bundle import Bundle
from repro.imaging import lowrank as lr
from repro.imaging import psf as psf_op
from repro.imaging import starlet
from repro.imaging.condat import SolverConfig, solve
from repro.imaging.deconvolve import deconvolve
from repro.imaging.scdl import SCDLConfig, train
from repro.data.synthetic import coupled_patches

settings.register_profile("ci", max_examples=10, deadline=None)
settings.load_profile("ci")

KEY = jax.random.PRNGKey(11)


# ------------------------------------------------------------- starlet
@given(n_scales=st.integers(1, 5), seed=st.integers(0, 100))
def test_starlet_perfect_reconstruction(n_scales, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (41, 41))
    co = starlet.decompose(x, n_scales)
    np.testing.assert_allclose(np.asarray(starlet.recompose(co)),
                               np.asarray(x), rtol=1e-4, atol=1e-5)


@given(n_scales=st.integers(1, 4), seed=st.integers(0, 100))
def test_starlet_adjoint_dot_product(n_scales, seed):
    """<Phi x, u> == <x, Phi^T u> to fp32 precision."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (32, 32))
    u = jax.random.normal(k2, (n_scales, 32, 32))
    lhs = float(jnp.sum(starlet.forward(x, n_scales) * u))
    rhs = float(jnp.sum(x * starlet.adjoint(u, n_scales)))
    assert abs(lhs - rhs) <= 1e-4 * max(abs(lhs), 1.0)


# ------------------------------------------------------------------ H
@given(seed=st.integers(0, 50))
def test_psf_operator_adjoint(seed):
    data = psf_op.simulate(4, jax.random.PRNGKey(seed))
    y = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), 1),
                          data.Y.shape)
    lhs = float(jnp.sum(psf_op.H(data.X_true, data.psfs) * y))
    rhs = float(jnp.sum(data.X_true * psf_op.Ht(y, data.psfs)))
    assert abs(lhs - rhs) <= 1e-4 * max(abs(lhs), 1.0)


def test_psf_convolve_matches_direct():
    """FFT convolution == direct convolution on a small case."""
    from scipy.signal import convolve2d
    x = np.asarray(jax.random.normal(KEY, (9, 9)), np.float64)
    k = np.zeros((9, 9)); k[3:6, 3:6] = np.random.RandomState(0).rand(3, 3)
    out = np.asarray(psf_op.convolve(jnp.array(x)[None],
                                     jnp.array(k, jnp.float32)[None]))[0]
    ref = convolve2d(x, k, mode="same")
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


# ------------------------------------------------- paired-FFT engine
def test_fast_pad_rule():
    """Derived grid: smallest 5-smooth size >= 2S - 1 (DESIGN.md §16)."""
    assert psf_op.fast_size(81) == 81          # 3^4
    assert psf_op.fast_size(82) == 90          # 2 * 3^2 * 5
    assert psf_op.pad_for(41) == 81            # the seed hardcoded 96
    assert psf_op.pad_for(64) == 128
    assert psf_op.pad_for(21) == 45
    for s in (9, 21, 33, 41, 57, 64):
        pad = psf_op.pad_for(s)
        assert pad >= 2 * s - 1
        assert psf_op.grid_of(psf_op.psf_fft_pair(
            jnp.ones((2, s, s)))) == pad


@pytest.mark.parametrize("stamp", [21, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv_pair_adjoint_property(stamp, dtype):
    """<H(x), y> == <x, Ht(y)> through conv_pair_f's two halves, at
    non-default stamp sizes on the derived pad, fp32 and bf16."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(stamp), 3)
    x = jax.random.normal(k1, (3, stamp, stamp), dtype)
    y = jax.random.normal(k2, (3, stamp, stamp), dtype)
    psfs = jax.random.normal(k3, (3, stamp, stamp), dtype)
    kf_pair = psf_op.psf_fft_pair(psfs)
    Hx, Hty = psf_op.conv_pair_f(x, y, kf_pair)
    lhs = float(jnp.sum(Hx.astype(jnp.float32) * y.astype(jnp.float32)))
    rhs = float(jnp.sum(x.astype(jnp.float32) * Hty.astype(jnp.float32)))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    assert abs(lhs - rhs) <= tol * max(abs(lhs), 1.0)


@pytest.mark.parametrize("stamp", [21, 41, 64])
def test_conv_pair_matches_single_calls(stamp):
    """The batched pair == separate H_f / Ht_f calls == the one-shot
    convolve API (kernel FFT recomputed per call)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(stamp + 1), 3)
    x = jax.random.normal(k1, (4, stamp, stamp))
    y = jax.random.normal(k2, (4, stamp, stamp))
    psfs = jax.random.normal(k3, (4, stamp, stamp))
    kf_pair = psf_op.psf_fft_pair(psfs)
    Hx, Hty = psf_op.conv_pair_f(x, y, kf_pair)
    np.testing.assert_allclose(np.asarray(Hx),
                               np.asarray(psf_op.H_fp(x, kf_pair)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(Hty),
                               np.asarray(psf_op.Ht_fp(y, kf_pair)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(Hx),
                               np.asarray(psf_op.H(x, psfs)),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(Hty),
                               np.asarray(psf_op.Ht(y, psfs)),
                               rtol=1e-4, atol=1e-5)


def test_derived_pad_matches_oversized_grid():
    """The fast pad (81 for S = 41) computes the identical 'same'
    convolution as a generously padded grid — the crop window is
    alias-free at 2S - 1 (DESIGN.md §16)."""
    data = psf_op.simulate(4, jax.random.PRNGKey(5))
    for pad in (96, 128):
        kf = psf_op.psf_fft(data.psfs, pad=pad)
        np.testing.assert_allclose(
            np.asarray(psf_op.H(data.X_true, data.psfs)),
            np.asarray(psf_op.H_f(data.X_true, kf)),
            rtol=1e-4, atol=1e-6)


def test_sparse_dual_overrelax_linearity():
    """Phi(2 X_new - X) == 2 Phi(X_new) - Phi(X): the identity that
    lets the solver carry Phi(X) and run one starlet forward per
    iteration (DESIGN.md §16)."""
    from repro.kernels.starlet2d import ops as starlet_batch
    k1, k2 = jax.random.split(KEY)
    X = jax.random.normal(k1, (6, 41, 41))
    Xn = jax.random.normal(k2, (6, 41, 41))
    direct = starlet_batch.forward(2 * Xn - X, 3)
    linear = 2 * starlet_batch.forward(Xn, 3) - starlet_batch.forward(X, 3)
    np.testing.assert_allclose(np.asarray(linear), np.asarray(direct),
                               rtol=1e-4, atol=1e-5)


# -------------------------------------------------- Algorithm 1 (PSF)
@pytest.fixture(scope="module")
def psf_data():
    return psf_op.simulate(8, jax.random.PRNGKey(2))


def test_sparse_deconvolution_improves_mse(psf_data):
    cfg = SolverConfig(mode="sparse", n_scales=3)
    X, costs = solve(psf_data.Y, psf_data.psfs, cfg,
                     sigma_noise=psf_data.sigma, n_iter=40)
    mse_obs = float(jnp.mean((psf_data.Y - psf_data.X_true) ** 2))
    mse_dec = float(jnp.mean((X - psf_data.X_true) ** 2))
    assert mse_dec < 0.2 * mse_obs
    assert float(costs[-1]) < float(costs[0])


def test_distributed_sparse_equals_sequential(psf_data):
    cfg = SolverConfig(mode="sparse", n_scales=3)
    _, costs = solve(psf_data.Y, psf_data.psfs, cfg,
                     sigma_noise=psf_data.sigma, n_iter=15)
    _, log = deconvolve(psf_data.Y, psf_data.psfs, cfg, mesh=None,
                        sigma_noise=psf_data.sigma, max_iter=15, tol=0)
    np.testing.assert_allclose(np.asarray(costs), np.asarray(log.costs),
                               rtol=1e-4)


def test_distributed_lowrank_converges(psf_data):
    """Primal-dual cost is not monotone; require recovery quality and a
    bounded, non-diverging trajectory instead."""
    cfg = SolverConfig(mode="lowrank", lam=0.05, rank=8)
    Xd, log = deconvolve(psf_data.Y, psf_data.psfs, cfg, mesh=None,
                         max_iter=25, tol=0)
    assert np.isfinite(log.costs).all()
    assert max(log.costs[5:]) <= log.costs[0] * 1.1
    mse_obs = float(jnp.mean((psf_data.Y - psf_data.X_true) ** 2))
    mse_dec = float(np.mean((Xd - np.asarray(psf_data.X_true)) ** 2))
    assert mse_dec < mse_obs


def test_randomized_svt_matches_exact():
    """Distributed randomized SVT == exact SVT on a low-rank matrix."""
    k1, k2 = jax.random.split(KEY)
    U = jax.random.normal(k1, (64, 5))
    V = jax.random.normal(k2, (5, 30))
    A = U @ V
    omega = lr.make_test_matrix(30, rank=8, key=KEY)
    exact = lr.svt(A, 0.5)
    approx = lr.randomized_svt_local(A, omega, 0.5, axes=None)
    np.testing.assert_allclose(np.asarray(approx), np.asarray(exact),
                               rtol=5e-3, atol=5e-3)


def test_nuclear_norm_rf_keeps_small_singular_values():
    """The range-finder nuclear norm (of the sketch X @ omega) counts
    every singular value above the fp32 rounding floor (~7e-4 of the
    largest), however small next to it, and none below."""
    rng = np.random.default_rng(3)
    U, _ = np.linalg.qr(rng.normal(size=(200, 6)))
    V, _ = np.linalg.qr(rng.normal(size=(60, 6)))
    s = np.array([1.0, 0.3, 0.05, 0.01, 3e-3, 1e-6])
    X = (U * s) @ V.T
    omega = lr.make_test_matrix(60, rank=8, key=KEY)
    sk = np.linalg.svd(X @ np.asarray(omega, np.float64), compute_uv=False)
    assert sk[4] > 3 * 7e-4 * sk[0] and sk[5] < 1e-5 * sk[0]
    nuc = float(lr.nuclear_norm_rf(jnp.asarray(X, jnp.float32), omega, ()))
    np.testing.assert_allclose(nuc, sk[:5].sum(), rtol=1e-4)


# -------------------------------------------------- Algorithm 2 (SCDL)
def _seed_scdl_reference(S_h, S_l, cfg, iters):
    """The pre-overhaul SCDL math, verbatim: per-iteration Gram rebuild +
    LU solves, separate outer einsums, unfused dual updates.  The parity
    oracle for the factor-once Cholesky/Woodbury rebuild."""
    from repro.imaging.scdl import init_dicts
    Xh, Xl = init_dicts(S_h, S_l, cfg)
    c1, c2, c3 = cfg.c1, cfg.c2, cfg.c3
    A = cfg.n_atoms
    K = S_h.shape[1]
    eye = jnp.eye(A)
    Sh, Sl = S_h.T, S_l.T
    Wh = Wl = P = Q = Y1 = Y2 = Y3 = jnp.zeros((K, A))
    soft = lambda x, t: jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)
    costs = []
    for _ in range(iters):
        Gh = 2.0 * Xh.T @ Xh + (c1 + c3) * eye
        Gl = 2.0 * Xl.T @ Xl + (c2 + c3) * eye
        rhs_h = 2.0 * Sh @ Xh + c1 * P + Y1 - Y3 + c3 * Wl
        Wh = jnp.linalg.solve(Gh, rhs_h.T).T
        rhs_l = 2.0 * Sl @ Xl + c2 * Q + Y2 + Y3 + c3 * Wh
        Wl = jnp.linalg.solve(Gl, rhs_l.T).T
        P = soft(Wh - Y1 / c1, cfg.lam_h / c1)
        Q = soft(Wl - Y2 / c2, cfg.lam_l / c2)
        Y1 = Y1 + c1 * (P - Wh)
        Y2 = Y2 + c2 * (Q - Wl)
        Y3 = Y3 + c3 * (Wh - Wl)
        phi_h, phi_l = Wh.T @ Wh, Wl.T @ Wl
        Xh = jnp.linalg.solve(phi_h + cfg.delta * eye, (Sh.T @ Wh).T).T
        Xl = jnp.linalg.solve(phi_l + cfg.delta * eye, (Sl.T @ Wl).T).T
        clip = lambda X: X / jnp.maximum(
            jnp.linalg.norm(X, axis=0, keepdims=True), 1.0)
        Xh, Xl = clip(Xh), clip(Xl)
        nrmse_h = jnp.sqrt(jnp.sum((Sh - Wh @ Xh.T) ** 2)
                           / (jnp.sum(Sh ** 2) + 1e-12))
        nrmse_l = jnp.sqrt(jnp.sum((Sl - Wl @ Xl.T) ** 2)
                           / (jnp.sum(Sl ** 2) + 1e-12))
        costs.append(float(0.5 * (nrmse_h + nrmse_l)))
    return np.asarray(Xh), np.asarray(Xl), np.asarray(costs)


def _clustered_patches(K, p_dim, m_dim, n_proto=4, seed=9):
    """Samples drawn from a few prototypes + tiny jitter: the random-
    column dictionary init then holds many near-duplicate atoms, so
    X^T X is nearly rank-``n_proto`` — the ill-conditioned regime the
    ridge Grams must survive."""
    rng = np.random.RandomState(seed)
    proto_h = rng.randn(p_dim, n_proto)
    proto_l = rng.randn(m_dim, n_proto)
    idx = rng.randint(0, n_proto, size=K)
    amp = rng.rand(K) + 0.5
    S_h = proto_h[:, idx] * amp + 1e-3 * rng.randn(p_dim, K)
    S_l = proto_l[:, idx] * amp + 1e-3 * rng.randn(m_dim, K)
    return (jnp.asarray(S_h, jnp.float32), jnp.asarray(S_l, jnp.float32))


def test_scdl_matches_seed_lu_math():
    """Factor-once Cholesky/Woodbury solves == the seed's per-iteration
    LU math (trajectory AND dictionaries, including the delta-damped
    dictionary update) on well-posed data.

    The trajectory tolerance is rtol 3e-4, not fp32 epsilon: this
    trajectory amplifies rounding about 10^3-fold.  A 1-ulp perturbation
    of S_h moves the seed path's own costs by up to 6e-5, and the jitted
    scan and the eager seed loop round differently on XLA:CPU; the two
    measured 1.4e-4 apart."""
    S_h, S_l = coupled_patches(256, 25, 9, 16, seed=5)
    cfg = SCDLConfig(n_atoms=16, max_iter=10)
    Xh_ref, Xl_ref, costs_ref = _seed_scdl_reference(S_h, S_l, cfg, 10)
    Xh, Xl, log = train(S_h, S_l, cfg, chunk=4)
    np.testing.assert_allclose(np.asarray(log.costs), costs_ref,
                               rtol=3e-4)
    np.testing.assert_allclose(Xh, Xh_ref, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(Xl, Xl_ref, rtol=1e-3, atol=1e-4)


def test_scdl_cholesky_path_matches_seed_lu_on_ill_conditioned():
    """Near-duplicate atoms: X^T X is nearly singular, only the ridge
    keeps the W systems solvable.  The trajectories must agree tightly
    while the NRMSE is well above the data's 1e-3 jitter floor.  Near
    the floor the problem is degenerate (near-duplicate atoms make the
    dictionary non-unique) and fp32 rounding picks the path: the seed
    math itself, jitted into a scan instead of run eagerly, ends at
    3.5e-3 where the eager loop ends at 1.4e-3.  There we require only
    that both actually solved the problem."""
    S_h, S_l = _clustered_patches(256, 25, 9)
    cfg = SCDLConfig(n_atoms=16, max_iter=10)
    _, _, costs_ref = _seed_scdl_reference(S_h, S_l, cfg, 10)
    Xh, Xl, log = train(S_h, S_l, cfg, chunk=4)
    costs = np.asarray(log.costs)
    above = costs_ref > 0.015      # more than 10x the jitter floor
    assert above.sum() >= 6
    np.testing.assert_allclose(costs[above], costs_ref[above], rtol=1e-2)
    # the well-posed head of the trajectory matches tightly
    np.testing.assert_allclose(costs[:4], costs_ref[:4], rtol=1e-3)
    assert costs[-1] < 0.01 and costs_ref[-1] < 0.01
    norms = np.linalg.norm(Xh, axis=0)
    assert (norms <= 1.0 + 1e-4).all()


def test_scdl_solve_factor_branches_match_lu():
    """All three factor-once regimes (thin Woodbury apply, dense inverse
    via Woodbury build, dense direct) equal a dense LU solve, on an
    ill-conditioned dictionary (near-duplicate atoms + ridge)."""
    from repro.imaging.scdl import _ridge_solve, _solve_factor
    key = jax.random.PRNGKey(3)
    for P, A in [(81, 512), (289, 512), (25, 16)]:
        base = jax.random.normal(key, (P, max(A // 8, 2)))
        X = jnp.repeat(base, 8, axis=1)[:, :A]
        X = X + 1e-3 * jax.random.normal(jax.random.fold_in(key, 1),
                                         (P, A))
        X = X / jnp.maximum(jnp.linalg.norm(X, axis=0, keepdims=True),
                            1e-8)
        S = jax.random.normal(jax.random.fold_in(key, 2), (128, P))
        Z = jax.random.normal(jax.random.fold_in(key, 3), (128, A))
        c = 1.2
        W = _ridge_solve(S, Z, X, _solve_factor(X, c), c)
        G = 2.0 * X.T @ X + c * jnp.eye(A)
        W_ref = jnp.linalg.solve(G, (2.0 * S @ X + Z).T).T
        np.testing.assert_allclose(np.asarray(W), np.asarray(W_ref),
                                   rtol=2e-4, atol=2e-4)


def test_scdl_converges_and_reconstructs():
    S_h, S_l = coupled_patches(512, 25, 9, 32, seed=4)
    cfg = SCDLConfig(n_atoms=32, max_iter=15)
    Xh, Xl, log = train(S_h, S_l, cfg)
    assert log.costs[-1] < 0.25 * log.costs[0]
    assert Xh.shape == (25, 32) and Xl.shape == (9, 32)
    norms = np.linalg.norm(Xh, axis=0)
    assert (norms <= 1.0 + 1e-4).all()


def test_scdl_cost_monotone_tail():
    S_h, S_l = coupled_patches(256, 25, 9, 16, seed=5)
    cfg = SCDLConfig(n_atoms=16, max_iter=12)
    _, _, log = train(S_h, S_l, cfg)
    # NRMSE after the burn-in should never regress by more than 5%
    tail = log.costs[3:]
    assert all(b <= a * 1.05 for a, b in zip(tail, tail[1:]))
