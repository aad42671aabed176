"""Algorithm 2 — Sparse Coupled Dictionary Learning over the bundle.

ADMM for Eq. (4): recover coupled low/high-resolution dictionaries
X_l, X_h and shared sparse codes from paired observations S_l, S_h.

Distribution (mirrors the paper's pseudo-code):
  1.   parallelise S_h, S_l over samples (K axis)        -> Bundle.create
  2/3. initialise dictionaries from random bundle samples -> init_dicts
  4/5. zip + enrich with W_h, W_l, Y1, Y2, Y3 (+ the folded
       splitting-term right-hand sides Z1, Z2)            -> same bundle
  6-10. per iteration:
     7. broadcast X_h, X_l + the factor-once solve operators for
        (2 X^T X + (c+c3) I)^-1 (DESIGN.md §13)
        -> replicated side of the bundle
     8. map: local W/P/Q/Y updates on each sample block
     9. map-reduce: psum outer products S W^T (P x A), W W^T (A x A)
        -> the all-reduce that replaces the paper's reduce-to-driver
    10. replicated dictionary update (Eq. 6-7) + column norm clipping

The sequential reference is the same step with an unpartitioned bundle —
used by tests to assert distributed == sequential math.

Factor-once broadcast (DESIGN.md §13): the ridge Gram matrices
``Gh = 2 Xh^T Xh + (c1+c3) I`` / ``Gl`` depend only on the replicated
dictionaries, so they are Cholesky-factored ONCE per iteration inside
the scan carry (:func:`make_refresh_fn`) instead of re-built and
LU-solved per partition per iteration.  The broadcast payload is the
factor *applied*: the explicit symmetric inverse when the patch
dimension dominates, or the Woodbury companion ``(c/2 I_P + X X^T)^-1 X``
when P < A (the GS/HS patch shapes: the Gram is a rank-P update of the
ridge), so every sample block's W solve is one or two GEMMs.

The splitting variables P, Q are not bundle state: step 8 only ever
consumes them through the right-hand-side combinations
``Z1 = c1 P + Y1 - Y3 + c3 Wl`` and ``Z2 = c2 Q + Y2 + Y3``, which the
fused elementwise kernel emits directly.  The multipliers and Z terms
live as ONE stacked (K, 5, A) leaf ``YZ = [Y1, Y2, Y3, Z1, Z2]`` so the
whole elementwise tail is one read/one write (kernels/admm_elwise).

Deviation note (DESIGN.md §9): the paper's Eq. (6-7) write the dictionary
update as X += S W^T/(phi + delta); we implement the standard damped
least-squares solve X = (S W^T)(phi + delta I)^-1 that this abbreviates
(Fotiadou et al.'s Alg. 1), with unit-norm column clipping per Eq. (4).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro.core.bundle import Bundle
from repro.core.problem import Problem, register, solve
from repro.imaging.lowrank import matmul_fp32 as _mm
from repro.kernels.admm_elwise.ops import admm_elwise
from repro.kernels.dict_outer.ops import dict_outer_pair

# Every product here runs at full fp32 precision (``_mm``): both the
# ridge solves and the NRMSE residual S - W X^T (a small difference of
# large terms) amplify a bf16 pass's rounding (measured on the chip,
# PERF.md).


@dataclass(frozen=True)
class SCDLConfig:
    n_atoms: int = 512             # A
    lam_h: float = 0.01
    lam_l: float = 0.01
    c1: float = 0.4
    c2: float = 0.4
    c3: float = 0.8
    delta: float = 1e-2
    max_iter: int = 100
    tol: float = 0.0               # paper runs to i_max


def init_dicts(S_h, S_l, cfg: SCDLConfig, key=None):
    """Steps 2/3: random sample columns -> initial unit-norm dictionaries."""
    key = key if key is not None else jax.random.PRNGKey(3)
    K = S_h.shape[1]
    idx = jax.random.choice(key, K, (cfg.n_atoms,), replace=False)
    X_h = S_h[:, idx]
    X_l = S_l[:, idx]
    X_h = X_h / jnp.maximum(jnp.linalg.norm(X_h, axis=0, keepdims=True), 1e-8)
    X_l = X_l / jnp.maximum(jnp.linalg.norm(X_l, axis=0, keepdims=True), 1e-8)
    return X_h, X_l


def _solve_factor(X, c):
    """Factor-once payload for applying ``(2 X^T X + c I)^-1`` (X: (P, A)).

    The Gram is a rank-P update of the ridge, so for P < A (the paper's
    patch shapes) the O(.^3) work happens on the (P, P) Woodbury
    companion ``B = c/2 I + X X^T``:

        (2 X^T X + c I)^-1 = (1/c) [I - X^T (c/2 I + X X^T)^-1 X]

    Three regimes, chosen by static shape (GEMM flops per K sample rows
    in brackets):

    - ``2P < A`` — *thin apply*: broadcast ``C = B^-1 X`` (P, A) and
      apply the bracketed form directly [4PA per row].
    - ``P < A <= 2P`` — *dense apply, Woodbury build*: materialise the
      (A, A) inverse from ``C`` (one (A, P)x(P, A) GEMM at build time),
      apply as a single square GEMM [2A^2 per row].
    - ``P >= A`` — *dense apply, direct build*: Cholesky the (A, A) Gram
      and solve against the identity.

    Dense payloads also carry ``B2 = 2 X G^-1`` so the per-block solve
    folds the right-hand-side assembly: ``w = (2 S X + Z) G^-1 =
    S B2 + Z G^-1`` — no rhs materialisation pass.  Either way the
    factorization happens once per iteration, in the replicated carry,
    not per partition (DESIGN.md §13).
    """
    P, A = X.shape
    eye = lambda n: jnp.eye(n, dtype=X.dtype)
    if P < A:
        B = 0.5 * c * eye(P) + _mm(X, X.T)
        C = jsl.cho_solve((jnp.linalg.cholesky(B), True), X)
        if 2 * P < A:
            return {"C": C}
        Gi = (eye(A) - _mm(X.T, C)) / c
    else:
        G = 2.0 * _mm(X.T, X) + c * eye(A)
        Gi = jsl.cho_solve((jnp.linalg.cholesky(G), True), eye(A))
    return {"Gi": Gi, "B2": 2.0 * _mm(X, Gi)}


def _ridge_solve(S, Z, X, F, c):
    """Row-wise solve ``(2 X^T X + c I) w = 2 S @ X + Z`` with the
    broadcast factor ``F`` from :func:`_solve_factor` — pure GEMMs on
    the sample block."""
    if "Gi" in F:
        return _mm(S, F["B2"]) + _mm(Z, F["Gi"])
    rhs = 2.0 * _mm(S, X) + Z
    return (rhs - _mm(_mm(rhs, X.T), F["C"])) / c


def broadcast_factors(Xh, Xl, cfg: SCDLConfig):
    """Step 7's broadcast payload: the dictionaries plus the factor-once
    solve operators for the W ridge systems."""
    return {"Xh": Xh, "Xl": Xl,
            "Fh": _solve_factor(Xh, cfg.c1 + cfg.c3),
            "Fl": _solve_factor(Xl, cfg.c2 + cfg.c3)}


def build_bundle(S_h, S_l, cfg: SCDLConfig, mesh=None, key=None
                 ) -> Bundle:
    """Steps 1-5: sample-axis bundle; record axis = K (transposed blocks).

    Beyond the paper's arrays the replicated side carries the solve
    factors (step 7) and the constant objective normalizers ||S||^2
    (recomputed every iteration in the seed; they never change)."""
    X_h, X_l = init_dicts(S_h, S_l, cfg, key)
    A = cfg.n_atoms
    K = S_h.shape[1]
    data = {
        "Sh": S_h.T, "Sl": S_l.T,              # (K, P) / (K, M)
        "Wh": jnp.zeros((K, A), S_h.dtype),    # (K, A) sample-major codes
        "Wl": jnp.zeros((K, A), S_h.dtype),
        # stacked multiplier state [Y1, Y2, Y3, Z1, Z2]
        "YZ": jnp.zeros((K, 5, A), S_h.dtype),
    }
    replicated = dict(broadcast_factors(X_h, X_l, cfg),
                      n_h=jnp.sum(S_h.astype(jnp.float32) ** 2),
                      n_l=jnp.sum(S_l.astype(jnp.float32) ** 2))
    return Bundle.create(data, mesh=mesh, replicated=replicated)


def _code_updates(d, rep, cfg: SCDLConfig):
    """Step 8: local ADMM updates for one sample block (all (K_loc, .)).

    The ridge systems are solved against the broadcast factor-once
    operators (GEMMs; the Gram build/factorization lives in
    :func:`make_refresh_fn`), and the soft-threshold + three dual
    updates run through the fused ``admm_elwise`` kernel — one read and
    one write of each (K_loc, A) array instead of ~5 full passes."""
    c1, c2, c3 = cfg.c1, cfg.c2, cfg.c3
    Wh = _ridge_solve(d["Sh"], d["YZ"][:, 3], rep["Xh"], rep["Fh"],
                      c1 + c3)
    Wl = _ridge_solve(d["Sl"], d["YZ"][:, 4] + c3 * Wh, rep["Xl"],
                      rep["Fl"], c2 + c3)

    YZ = admm_elwise(Wh, Wl, d["YZ"], c1=c1, c2=c2, c3=c3,
                     t1=cfg.lam_h / c1, t2=cfg.lam_l / c2)
    return dict(d, Wh=Wh, Wl=Wl, YZ=YZ)


def _outer_products(d, axes):
    """Step 9: psum-reduced S W^T and W W^T (the paper's map-reduce).

    Both coupled pairs run through the fused ``dict_outer_pair`` kernel:
    each (block_k, A) code tile is read from HBM once and feeds both its
    S^T W and W^T W accumulators while resident in VMEM."""
    ShWh, SlWl, phi_h, phi_l = dict_outer_pair(
        d["Sh"], d["Sl"], d["Wh"], d["Wl"])
    parts = {"ShWh": ShWh, "SlWl": SlWl, "phi_h": phi_h, "phi_l": phi_l}
    if axes:
        parts = jax.tree.map(lambda x: jax.lax.psum(x, axes), parts)
    return parts


def _dict_update(rep, outer, cfg: SCDLConfig):
    """Step 10 / Eq. (6-7): damped LS dictionary update + column norms.

    ``phi + delta I`` is SPD (phi = W^T W is PSD, delta > 0), so the
    damped solve goes through Cholesky as well."""
    A = rep["Xh"].shape[1]
    eye = jnp.eye(A, dtype=rep["Xh"].dtype)
    dt = rep["Xh"].dtype
    ch = jnp.linalg.cholesky(outer["phi_h"].astype(dt) + cfg.delta * eye)
    cl = jnp.linalg.cholesky(outer["phi_l"].astype(dt) + cfg.delta * eye)
    Xh = jsl.cho_solve((ch, True), outer["ShWh"].T.astype(dt)).T
    Xl = jsl.cho_solve((cl, True), outer["SlWl"].T.astype(dt)).T
    clip = lambda X: X / jnp.maximum(
        jnp.linalg.norm(X, axis=0, keepdims=True), 1.0)
    return {"Xh": clip(Xh), "Xl": clip(Xl)}


def _iterate(d, rep, axes, cfg: SCDLConfig):
    """Steps 8-10 minus the objective: the shared body of the full and
    cost-free step variants."""
    d = _code_updates(d, rep, cfg)
    outer = _outer_products(d, axes)
    new_dicts = _dict_update(rep, outer, cfg)
    return d, new_dicts


def make_step_fn(cfg: SCDLConfig):
    """One full ADMM iteration (steps 7-10) as a bundle step.

    Returns (new_data, {"cost", "nrmse_h", "nrmse_l", "Xh", "Xl"}): the
    dictionaries ride in the reduced output (replicated), feeding the
    next iteration's broadcast — the driver folds them (and the
    factor-once solve operators) back into the replicated side via
    :func:`make_refresh_fn`.
    """

    def step(d, rep, axes):
        d, new_dicts = _iterate(d, rep, axes, cfg)
        # augmented-Lagrangian data terms (the paper's Fig. 14 metric is
        # the reconstruction error of the *calculated dictionaries*)
        res_h = jnp.sum((d["Sh"] - _mm(d["Wh"], new_dicts["Xh"].T)) ** 2)
        res_l = jnp.sum((d["Sl"] - _mm(d["Wl"], new_dicts["Xl"].T)) ** 2)
        parts = {"res_h": res_h, "res_l": res_l}
        if axes:
            parts = jax.tree.map(lambda x: jax.lax.psum(x, axes), parts)
        nrmse_h = jnp.sqrt(parts["res_h"] / (rep["n_h"] + 1e-12))
        nrmse_l = jnp.sqrt(parts["res_l"] / (rep["n_l"] + 1e-12))
        out = {"cost": 0.5 * (nrmse_h + nrmse_l),
               "nrmse_h": nrmse_h, "nrmse_l": nrmse_l, **new_dicts}
        return d, out

    return step


def make_light_step_fn(cfg: SCDLConfig):
    """The same iteration without the objective evaluation — the
    ``cost_every`` fast path.  Skips the full (K_loc, P)/(K_loc, M)
    reconstructions ``Wh @ Xh^T`` / ``Wl @ Xl^T`` that exist only for the
    NRMSE trace.  Returns ``(data', {"Xh", "Xl"})`` so the dictionary
    update still reaches the broadcast carry every iteration
    (``light_updates_replicated`` in ``core.engine.make_scan_step``)."""

    def step(d, rep, axes):
        return _iterate(d, rep, axes, cfg)

    return step


def make_cost_fn(cfg: SCDLConfig):
    """Standalone NRMSE objective over the post-iteration state — the
    per-chunk cost mode (``core.engine.make_chunk_cost_step``).  The
    refreshed broadcast carry holds the iteration's dictionaries, so
    this computes exactly the numbers the full step would have logged
    for the chunk's final iteration."""

    def cost(d, rep, axes):
        res_h = jnp.sum((d["Sh"] - _mm(d["Wh"], rep["Xh"].T)) ** 2)
        res_l = jnp.sum((d["Sl"] - _mm(d["Wl"], rep["Xl"].T)) ** 2)
        parts = {"res_h": res_h, "res_l": res_l}
        if axes:
            parts = jax.tree.map(lambda x: jax.lax.psum(x, axes), parts)
        nrmse_h = jnp.sqrt(parts["res_h"] / (rep["n_h"] + 1e-12))
        nrmse_l = jnp.sqrt(parts["res_l"] / (rep["n_l"] + 1e-12))
        return {"cost": 0.5 * (nrmse_h + nrmse_l),
                "nrmse_h": nrmse_h, "nrmse_l": nrmse_l}

    return cost


def make_refresh_fn(cfg: SCDLConfig):
    """Step 7's per-iteration broadcast: fold the reduced dictionary
    update back into the replicated state AND post-process it into the
    factor-once solve operators (Gram/companion build + Cholesky +
    ``cho_solve``).  Runs inside the fused scan carry
    (``core.engine.make_scan_step``), so neither the dictionaries nor
    their factors ever leave the device between iterations."""

    def refresh(rep, out):
        return dict(rep, **broadcast_factors(out["Xh"], out["Xl"], cfg))

    return refresh


@register("scdl")
class SCDLProblem(Problem):
    """Algorithm 2, declared once (DESIGN.md §14).

    The dictionaries (and their factor-once solve operators) are part of
    the iterate, not of the objective — ``replicated_in_carry`` makes
    the derived wiring advance the broadcast state on *every* iteration
    (``light_updates_replicated``), and the declared ``cost`` enables
    the per-chunk objective mode ``cost_every="chunk"``.
    """

    replicated_in_carry = True

    def __init__(self, cfg: Optional[SCDLConfig] = None, key=None):
        self.cfg = cfg if cfg is not None else SCDLConfig()
        self.key = key
        self._step = make_step_fn(self.cfg)
        self._light = make_light_step_fn(self.cfg)
        self._cost = make_cost_fn(self.cfg)
        self._refresh = make_refresh_fn(self.cfg)

    def init_bundle(self, inputs, mesh) -> Bundle:
        S_h, S_l = inputs
        return build_bundle(S_h, S_l, self.cfg, mesh=mesh, key=self.key)

    def full_step(self, d, rep, axes):
        return self._step(d, rep, axes)

    def light_step(self, d, rep, axes):
        return self._light(d, rep, axes)

    def cost(self, d, rep, axes):
        return self._cost(d, rep, axes)

    def refresh_replicated(self, rep, out):
        return self._refresh(rep, out)

    def finalize(self, bundle, log):
        Xh = jax.device_get(bundle.replicated["Xh"])
        Xl = jax.device_get(bundle.replicated["Xl"])
        return (Xh, Xl), {}

    def batch_axes(self):
        from repro.core.batching import BatchAxes
        # samples live on axis 1 of the raw (P, K)/(M, K) patch
        # matrices.  No record padding: the per-iteration Gram matrices
        # reduce over the sample axis, and although zero columns add
        # nothing analytically, the dictionaries are part of the carry
        # and sensitive to the reduction's floating-point grouping —
        # instances bucket on exact K instead.  The dictionaries and
        # their factor caches are per-instance iterate state, so
        # nothing is shared across a bucket.
        return BatchAxes(record_axes=(1, 1), pad_records=False,
                        instance_invariant=("key",))


def train(S_h, S_l, cfg: SCDLConfig, mesh=None, key=None,
          max_iter: Optional[int] = None, chunk: int = 8,
          cost_every=1):
    """End-to-end Algorithm 2. Returns (X_h*, X_l*, log).

    ``cost_every=k`` evaluates the NRMSE objective every k-th iteration
    only (the iterates are unaffected; off-grid log entries carry the
    last evaluated value forward, DESIGN.md §12).  ``cost_every="chunk"``
    is the fastest observability mode: one objective evaluation per
    dispatched chunk, on its final state — the granularity the driver
    checks convergence at anyway (DESIGN.md §13).

    .. deprecated:: PR 4
        Thin shim over ``solve(SCDLProblem(cfg, key), S_h, S_l)``
        (bit-identical wiring); use the ``solve()`` entry point.
    """
    warnings.warn(
        "scdl.train(...) is deprecated; use repro.core.problem.solve("
        '"scdl", S_h, S_l, cfg=cfg, ...) (DESIGN.md §14)',
        DeprecationWarning, stacklevel=2)
    sol = solve(SCDLProblem(cfg, key=key), S_h, S_l, mesh=mesh,
                max_iter=max_iter, chunk=chunk, cost_every=cost_every)
    Xh, Xl = sol.x
    return Xh, Xl, sol.log
