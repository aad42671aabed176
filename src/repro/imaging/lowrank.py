"""Nuclear-norm proximal operators for the low-rank deconvolution (Eq. 3).

Sequential reference: full SVD of the (n_images, S*S) pixel matrix —
exactly what the paper's driver does after reassembling the stack, and
exactly why its low-rank speedup saturates at 1.2-2.5x.

Distributed version (beyond-paper, DESIGN.md §2): randomized range-finder
SVT that never gathers the stack.  All cross-partition traffic is two
psum-reduced Gram/projection matrices of size (r, r) and (r, p):

    Y = A @ Omega                    (local rows)
    Q = Y chol(Y^T Y)^-T             (Y^T Y psum, r x r)
    B = Q^T A                        (psum, r x p)
    U S V^T = svd(B)                 (replicated, tiny)
    A_svt = (Q U) max(S - t, 0) V^T  (local rows)

The iteration count of the enclosing primal-dual loop tolerates the
range-finder approximation (rank r chosen >= expected galaxy-stack rank).

Beyond the operators, this module declares a third first-class workload
on the generic engine (DESIGN.md §14): :class:`LowRankCompletionProblem`
(registered ``"lowrank"``) — distributed low-rank matrix completion via
proximal gradient + the randomized SVT above.  It exists to prove the
Problem API generalizes beyond the paper's two use cases: the entire
workload is the <50-line declaration at the bottom of this file.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.bundle import Bundle, gather
from repro.core.problem import Problem, register


def matmul_fp32(a, b):
    """``a @ b`` at full fp32 precision.  On TPU an f32 dot defaults to
    a single bf16 pass; the range finder's (r, r) Gram squares the
    conditioning of what that rounds (measured on the chip, PERF.md),
    and SCDL and the PSF operator's DFTs use it too."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


_mm = matmul_fp32


def svt(mat: jax.Array, thresh) -> jax.Array:
    """Exact singular-value thresholding (sequential reference)."""
    u, s, vt = jnp.linalg.svd(mat, full_matrices=False)
    s = jnp.maximum(s - thresh, 0.0)
    return (u * s[None, :]) @ vt


def _orthonormalize(y, axes, eps):
    """Orthonormal basis of the row-sharded ``y``'s column space through
    the psum-reduced Gram's eigendecomposition (rank-deficient safe:
    null directions are clipped, unlike a regularised Cholesky)."""
    gram = _mm(y.T, y)                               # (r, r)
    if axes:
        gram = jax.lax.psum(gram, axes)
    evals, evecs = jnp.linalg.eigh(gram)
    scale = jnp.where(evals > eps * jnp.max(evals),
                      jax.lax.rsqrt(jnp.maximum(evals, 1e-30)), 0.0)
    return _mm(y, evecs * scale[None, :])


@jax.named_scope("svt")
def randomized_svt_local(a_local: jax.Array, omega: jax.Array, thresh,
                         axes=None, eps: float = 1e-6) -> jax.Array:
    """SVT of the row-sharded matrix from inside a shard_map/bundle_map.

    a_local: (n_local, p) rows of A; omega: (p, r) replicated test matrix;
    ``axes``: mesh axes to psum over (None == single partition).

    The basis is orthonormalised twice (CholeskyQR2): the Gram squares
    the condition number, so one pass leaves directions near the ``eps``
    cut-off off-orthogonal by up to ``eps_f32 / eps`` — a non-projection
    that fp32 rounding then steers.  The second pass costs one more
    (r, r) psum and restores orthogonality to working precision.
    """
    y = _mm(a_local, omega)                          # (n_loc, r)
    q = _orthonormalize(_orthonormalize(y, axes, eps), axes, eps)
    b = _mm(q.T, a_local)                            # (r, p)
    if axes:
        b = jax.lax.psum(b, axes)
    u, s, vt = jnp.linalg.svd(b, full_matrices=False)
    s = jnp.maximum(s - thresh, 0.0)
    return _mm(_mm(q, u) * s[None, :], vt)           # (n_loc, p)


def make_test_matrix(p: int, rank: int, oversample: int = 8,
                     key: Optional[jax.Array] = None) -> jax.Array:
    key = key if key is not None else jax.random.PRNGKey(7)
    return jax.random.normal(key, (p, rank + oversample)) / jnp.sqrt(p)


# ---------------------------------------------------------------------
# Workload: distributed low-rank matrix completion
# ---------------------------------------------------------------------

@dataclass(frozen=True)
class CompletionConfig:
    """min_X 0.5||M o (X - Y)||_F^2 + lam ||X||_* by proximal gradient:
    X <- SVT(X - step * M o (X - Y), lam * step), SVT distributed via
    the randomized range finder (rows of X sharded, two psums/iter)."""
    rank: int = 16                 # range-finder target rank
    lam: float = 0.1               # nuclear-norm weight
    step: float = 1.0              # <= 1/L; L = 1 for the masked id.
    oversample: int = 8
    max_iter: int = 200
    tol: float = 1e-4


# Gram eigenvalues within this many ulps of the largest are rounding
NOISE_ULPS = 4


def _masked_residual(d):
    return d["M"] * (d["X"] - d["Y"])


def nuclear_norm_rf(X_loc, omega, axes):
    """Range-finder nuclear norm of a row-sharded matrix: the nuclear
    norm of its sketch ``X @ omega`` (psum-reduced (r, r) Gram of the
    projection, replicated eigen-sqrt-sum), which captures every
    direction of X when rank(X) <= r, e.g. for every post-SVT iterate.
    Shared by the lowrank-mode deconvolution objective and the
    completion workload.

    Gram eigenvalues below a few ulps of the largest are rounding, not
    spectrum (their square roots would add about sqrt(eps) * sigma_1
    each, and move with any 1-ulp change of the input), so they count
    as zero: singular values below about 7e-4 * sigma_1 in fp32."""
    y = _mm(X_loc, omega)
    gram = _mm(y.T, y)
    if axes:
        gram = jax.lax.psum(gram, axes)
    s2 = jnp.linalg.eigvalsh(gram)
    floor = NOISE_ULPS * jnp.finfo(s2.dtype).eps * jnp.max(s2)
    s2 = jnp.where(s2 > floor, s2, 0.0)
    return jnp.sum(jnp.sqrt(s2))


@register("lowrank")
class LowRankCompletionProblem(Problem):
    """Low-rank completion of a row-sharded matrix, declared once.

    Inputs: ``(Y, M)`` — observations (n, p) and a {0,1} mask of the
    same shape.  The broadcast side carries only the constant SVT test
    matrix, so there is no ``refresh_replicated``; the declared
    ``light_step`` + ``cost`` unlock every objective cadence the engine
    offers (integer ``cost_every`` and ``"chunk"``).
    """

    def __init__(self, cfg: Optional[CompletionConfig] = None, key=None):
        self.cfg = cfg if cfg is not None else CompletionConfig()
        self.key = key

    def init_bundle(self, inputs, mesh) -> Bundle:
        Y, M = inputs
        M = jnp.asarray(M, Y.dtype)
        data = {"Y": Y * M, "M": M, "X": Y * M}
        omega = make_test_matrix(Y.shape[1], self.cfg.rank,
                                 self.cfg.oversample, key=self.key)
        return Bundle.create(data, mesh=mesh,
                             replicated={"omega": omega.astype(Y.dtype)})

    def _iterate(self, d, rep, axes):
        cfg = self.cfg
        X_half = d["X"] - cfg.step * _masked_residual(d)
        X_new = randomized_svt_local(X_half, rep["omega"],
                                     cfg.lam * cfg.step, axes=axes or None)
        return dict(d, X=X_new)

    def full_step(self, d, rep, axes):
        d_new = self._iterate(d, rep, axes)
        out = self.cost(d_new, rep, axes)
        return d_new, out

    def light_step(self, d, rep, axes):
        return self._iterate(d, rep, axes)

    def cost(self, d, rep, axes):
        data_part = 0.5 * jnp.sum(_masked_residual(d) ** 2)
        if axes:
            data_part = jax.lax.psum(data_part, axes)
        nuc = nuclear_norm_rf(d["X"], rep["omega"], axes)
        return {"cost": data_part + self.cfg.lam * nuc}

    def finalize(self, bundle, log):
        return gather(bundle)["X"], {}

    def batch_axes(self):
        from repro.core.batching import BatchAxes
        # (Y, M) are row-major; the SVT test matrix is drawn from a
        # fixed key + config shape only, so one copy serves the bucket.
        # ``key`` is a constructor attribute shared by declaration.
        return BatchAxes(record_axes=(0, 0), shared_in_batch=("omega",),
                         instance_invariant=("key",))
