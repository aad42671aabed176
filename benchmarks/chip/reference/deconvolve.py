"""Plain reference of PSF deconvolution (Farrens et al. 2017, Eq. 2
and 3, solved by Condat's primal-dual iterations), in jax.numpy with
every product at a stated precision, sharing no code with the program.

Sparse mode:

    min_X 0.5 ||Y - H(X)||^2 + ||W o Phi(X)||_1   s.t.  X >= 0

    grad  = H^T (H X - Y)
    X'    = max(X - tau (grad + Phi^T U), 0)
    U'    = clip(U + sig Phi(2 X' - X), -W, W)

Low-rank mode, over the (stamps x pixels) matrix of the population:

    min_X 0.5 ||Y - H(X)||^2 + lam ||X||_*        s.t.  X >= 0

    X'    = max(X - tau (grad + U), 0)
    V     = U + sig (2 X' - X)
    U'    = V - sig SVT(V / sig, lam / sig)

where SVT thresholds the singular values of the range finder's
projection (the program's stated algorithm: a Gaussian test matrix of
key 7 with rank + 8 columns, scaled by 1 / sqrt(pixels)); the basis
comes from a QR factorisation here, from a twice-repeated Gram
eigendecomposition in the program.

H convolves each stamp with its own PSF (``dft.py``).  Phi is the
starlet transform's detail scales with periodic borders, applied as
products with circulant smoothing matrices (the program shifts and
adds), and Phi^T as the sum of the scales' transposes.

The step sizes and weights follow the program's stated recipe, with
the same random draws, so that the reference solves the same problem:
||H|| by 60 power steps of [[0, H^T], [H, 0]] over the whole population
from normal draws of key 0; ||Phi|| by 30 power steps of Phi^T Phi from
a normal draw of key 0; the noise level of scale j as the standard
deviation of Phi_j over 8 white-noise stamps of key 1.  Computed
exactly instead, they differ by 0.3% (||H||), 0.2% (||Phi||) and up to
9% (scale weights), which moves the answer more than rounding does.

The check compares the final iterate of a sample of stamps, drawn from
the seed, after as many iterations as the timed solve ran.  Sparse
mode iterates the sampled stamps alone (each stamp's iterations are its
own once tau, sig and W are known); low-rank mode couples the stamps
through the SVT and iterates the whole population.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import dft

B3 = np.array([1.0, 4.0, 6.0, 4.0, 1.0]) / 16.0


def circulant(s: int, scale: int) -> jnp.ndarray:
    """B3 smoothing along one axis at dyadic ``scale``, periodic."""
    c = np.zeros((s, s))
    for t, w in enumerate(B3):
        for m in range(s):
            c[m, (m + (t - 2) * (1 << scale)) % s] += w
    return jnp.asarray(c, jnp.float32)


def _smooth(x, c, prec):
    y = dft.einsum("ab,...bc->...ac", c, x, prec)
    return dft.einsum("...ac,dc->...ad", y, c, prec)


def phi(x, n_scales, prec):
    """Detail scales: (..., s, s) -> (n_scales, ..., s, s)."""
    s = x.shape[-1]
    out, c = [], x
    for j in range(n_scales):
        nxt = _smooth(c, circulant(s, j), prec)
        out.append(c - nxt)
        c = nxt
    return jnp.stack(out)


def phi_t(u, n_scales, prec):
    """Transpose of :func:`phi`: every smoothing is symmetric and they
    commute, so scale j's operator (I - S_j) S_{j-1} ... S_0 is its own
    transpose."""
    s = u.shape[-1]
    total = 0.0
    for j in range(n_scales):
        v = u[j]
        for i in range(j):
            v = _smooth(v, circulant(s, i), prec)
        total = total + v - _smooth(v, circulant(s, j), prec)
    return total


@partial(jax.jit, static_argnames=("n_scales", "prec"))
def _norms(psfs, *, n_scales, prec):
    s = psfs.shape[-1]
    spec = dft.spectra(psfs, prec)
    ku, kv = jax.random.split(jax.random.PRNGKey(0))
    u = jax.random.normal(ku, psfs.shape)
    v = jax.random.normal(kv, psfs.shape)
    n0 = jnp.sqrt(jnp.sum(u ** 2) + jnp.sum(v ** 2))

    def power_h(_, c):
        u, v, _ = c
        hu = dft.convolve(u, spec, prec)
        htv = dft.convolve(v, spec, prec, adjoint=True)
        nrm = jnp.sqrt(jnp.sum(htv ** 2) + jnp.sum(hu ** 2)) + 1e-12
        return htv / nrm, hu / nrm, nrm

    *_, norm_h = jax.lax.fori_loop(0, 60, power_h,
                                   (u / n0, v / n0, jnp.float32(0)))

    def power_l(_, c):
        x, _ = c
        x2 = phi_t(phi(x, n_scales, prec), n_scales, prec)
        nrm = jnp.linalg.norm(x2)
        return x2 / (nrm + 1e-12), nrm

    x0 = jax.random.normal(jax.random.PRNGKey(0), (s, s))
    _, nl2 = jax.lax.fori_loop(0, 30, power_l, (x0, jnp.float32(0)))
    noise = jax.random.normal(jax.random.PRNGKey(1), (8, s, s))
    scale_std = jnp.std(phi(noise, n_scales, prec), axis=(1, 2, 3))
    return norm_h, jnp.sqrt(nl2), scale_std


def constants(psfs, sv: dict, prec):
    """tau, sig and the per-scale noise levels (the solver's recipe)."""
    norm_h, norm_l, scale_std = _norms(psfs, n_scales=sv["n_scales"],
                                       prec=prec)
    norm_h, norm_l = float(norm_h), float(norm_l)
    sig = sv["sigma_dual"] or 0.5 / max(norm_l ** 2, 1e-12)
    tau = sv["tau"] or 1.0 / (norm_h ** 2 / 2 + sig * norm_l ** 2 + 1e-12)
    return tau, sig, scale_std


@partial(jax.jit, static_argnames=("n_scales", "prec"))
def iterate(Y, psfs, weights, tau, sig, n_iter, *, n_scales, prec):
    """X after ``n_iter`` Condat iterations from X0 = H^T Y, U0 = 0."""
    spec = dft.spectra(psfs, prec)
    X0 = dft.convolve(Y, spec, prec, adjoint=True)

    def body(_, c):
        X, U = c
        grad = dft.convolve(dft.convolve(X, spec, prec) - Y, spec, prec,
                            adjoint=True)
        Xn = jnp.maximum(X - tau * (grad + phi_t(U, n_scales, prec)), 0.0)
        Un = jnp.clip(U + sig * phi(2.0 * Xn - X, n_scales, prec),
                      -weights, weights)
        return Xn, Un

    U0 = jnp.zeros((n_scales,) + Y.shape, Y.dtype)
    X, _ = jax.lax.fori_loop(0, n_iter, body, (X0, U0))
    return X


def svt(A, omega, thresh, prec):
    """Singular-value thresholding of A's projection on the range of
    A omega."""
    q, _ = jnp.linalg.qr(dft.einsum("np,pr->nr", A, omega, prec))
    b = dft.einsum("nr,np->rp", q, A, prec)
    u, s, vt = jnp.linalg.svd(b, full_matrices=False)
    qu = dft.einsum("nr,rk->nk", q, u, prec)
    return dft.einsum("nk,kp->np", qu * jnp.maximum(s - thresh, 0.0), vt,
                      prec)


@partial(jax.jit, static_argnames=("rank", "prec"))
def iterate_lowrank(Y, psfs, tau, sig, lam, n_iter, *, rank, prec):
    """X after ``n_iter`` low-rank Condat iterations from X0 = H^T Y,
    U0 = 0, over the whole population."""
    n, s = Y.shape[0], Y.shape[-1]
    p = s * s
    omega = jax.random.normal(jax.random.PRNGKey(7), (p, rank + 8)) \
        / np.sqrt(p)
    spec = dft.spectra(psfs, prec)
    X0 = dft.convolve(Y, spec, prec, adjoint=True)

    def body(_, c):
        X, U = c
        grad = dft.convolve(dft.convolve(X, spec, prec) - Y, spec, prec,
                            adjoint=True)
        Xn = jnp.maximum(X - tau * (grad + U), 0.0)
        V = U + sig * (2.0 * Xn - X)
        low = svt((V / sig).reshape(n, p), omega, lam / sig, prec)
        return Xn, V - sig * low.reshape(V.shape)

    X, _ = jax.lax.fori_loop(0, n_iter, body, (X0, jnp.zeros_like(Y)))
    return X


def sample(cell, seed: int) -> np.ndarray:
    n = cell.records
    k = min(int(cell.traffic["check"]["sample"]), n)
    return np.sort(np.random.default_rng(seed).choice(n, k, replace=False))


def observe(cell, sol, seed: int) -> dict:
    """What the check needs from the timed solve, taken before its state
    is freed: the sampled stamps of the final iterate."""
    idx = sample(cell, seed)
    return {"idx": idx, "x": np.asarray(sol.x)[idx]}


def reference(cell, inputs, observed: dict, iters: int, prec):
    """The reference's final iterate of the sampled stamps."""
    Y, psfs = inputs
    sv = cell.solver
    idx = jnp.asarray(observed["idx"])
    if sv["mode"] == "lowrank":
        norm_h = float(_norms(psfs, n_scales=1, prec=prec)[0])
        sig = sv["sigma_dual"] or 0.5
        tau = sv["tau"] or 1.0 / (norm_h ** 2 / 2 + sig + 1e-12)
        X = iterate_lowrank(Y, psfs, jnp.float32(tau), jnp.float32(sig),
                            jnp.float32(sv["lam"]), iters, rank=sv["rank"],
                            prec=prec)
        return np.asarray(X[idx])
    tau, sig, scale_std = constants(psfs, sv, prec)
    Ys, Ps = Y[idx], psfs[idx]
    energy = jnp.sqrt(jnp.sum(Ps ** 2, axis=(-2, -1)))
    weights = (sv["k_sigma"] * cell.problem_args["sigma_noise"]
               * scale_std[:, None] * energy[None, :])[..., None, None]
    return np.asarray(iterate(Ys, Ps, weights, jnp.float32(tau),
                              jnp.float32(sig), iters,
                              n_scales=sv["n_scales"], prec=prec))


def compare(observed_x: np.ndarray, ref_x: np.ndarray) -> dict:
    """x_gap: the widest gap of a sampled stamp from the reference, as a
    share of that stamp's peak in the reference."""
    x = np.asarray(observed_x, np.float64)
    r = np.asarray(ref_x, np.float64)
    peak = np.maximum(np.abs(r).max(axis=(-2, -1)), 1e-30)
    gap = np.abs(x - r).max(axis=(-2, -1)) / peak
    return {"x_gap": float(np.max(gap)) if np.all(np.isfinite(x))
            else float("inf")}


def check(cell, inputs, observed: dict, iters: int,
          prec: str = "highest") -> dict:
    return compare(observed["x"],
                   reference(cell, inputs, observed, iters, prec))
