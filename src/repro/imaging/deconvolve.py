"""Algorithm 1 — distributed space-variant PSF deconvolution.

Mirrors the paper's pseudo-code line by line:

  1. initialise X_p, X_d; extract H            -> simulate/Ht warm start
  2. parallelise Y, PSF, X_p, X_d into RDDs    -> Bundle.create
  3. sparse: map PSF -> W^(k)                  -> weight blocks in bundle
  4/5. zip into the bundled RDD D              -> one pytree, co-sharded
  6-11. iterate: map(update), map-reduce(cost) -> ONE shard_map step with
        a psum for the cost (and, for low-rank, two psums inside the
        distributed randomized SVT — the beyond-paper replacement for the
        paper's gather-to-driver SVD, DESIGN.md §2)
  12. save D / return X_p*                     -> gather()

The per-record math is imported from ``condat`` unchanged — the paper's
re-usability property of the Bundle/Unbundle design.  The iteration loop
itself runs chunked on-device (``chunk`` iterations per dispatch,
DESIGN.md §12); ``make_light_step_fn`` is the cost-free step used to
skip the objective evaluation off the ``cost_every`` grid.

Each layer of the iteration runs under a ``jax.named_scope`` — the
HLO ``op_name`` path the profiler's device trace names it by:
``condat`` (the primal/dual updates and their layout glue),
``psf_operator`` (``psf.H_fp``/``Ht_fp``/``conv_pair_f``), ``starlet``
(the batched transforms), ``svt`` (the randomized SVT) and
``objective`` (the cost and its psums).  The innermost scope names an
op; scopes change metadata only, not the compiled program.

The workload is declared once as :class:`DeconvolutionProblem`
(registered under ``"deconvolve"``, DESIGN.md §14) and run through the
generic ``repro.core.problem.solve`` entry point; the original
``deconvolve(...)`` signature survives as a deprecation shim over it.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.bundle import Bundle, gather
from repro.core.problem import Problem, register, solve
from repro.imaging import lowrank as lr
from repro.imaging import psf as psf_op
from repro.imaging import starlet
from repro.imaging.condat import (SolverConfig, data_cost_from,
                                  grad_from_HX, primal_update,
                                  sparse_dual_adjoint, sparse_dual_update,
                                  sparse_reg_cost, step_sizes)
from repro.kernels.condat_elwise.ops import condat_primal
from repro.kernels.starlet2d import ops as starlet_batch


def build_bundle(Y, psfs, cfg: SolverConfig, mesh=None,
                 sigma_noise: float = 0.02) -> Tuple[Bundle, dict]:
    """Steps 1-5: parallelise + zip the inputs into the bundled RDD.

    Beyond the paper's five arrays, the bundle carries three derived
    co-partitioned leaves that make each iteration cheaper (DESIGN.md
    §16): ``psf_fp`` (the (kf, conj kf) kernel-spectrum pair on the
    derived fast pad, constant across iterations), ``HX`` (the forward
    model of the current primal, reused by the next iteration's gradient
    so H runs once per iteration, not twice) and — sparse mode — ``CX``
    (the starlet stack Phi(X), so the over-relaxed dual input is the
    linear combination 2 Phi(X_new) - Phi(X) and one transform per
    iteration serves dual update and objective alike).
    """
    kf_pair = psf_op.psf_fft_pair(psfs)
    tau, sig, W = step_sizes(Y, psfs, cfg, sigma_noise, kf_pair=kf_pair)
    X0 = psf_op.Ht_fp(Y, kf_pair)
    data = {"Y": Y, "psf_fp": kf_pair, "Xp": X0,
            "HX": psf_op.H_fp(X0, kf_pair)}
    if cfg.mode == "sparse":
        # step 3: the weighting blocks are a *map over the PSF blocks*;
        # stored record-major (n, J, 1, 1) so they co-partition with Y.
        data["W"] = jnp.swapaxes(W, 0, 1)
        data["Xd"] = jnp.zeros((Y.shape[0], cfg.n_scales) + Y.shape[1:])
        data["CX"] = jnp.swapaxes(
            starlet_batch.forward(X0, cfg.n_scales), 0, 1)
    else:
        data["Xd"] = jnp.zeros_like(Y)
    replicated = {"tau": jnp.float32(tau), "sig": jnp.float32(sig)}
    if cfg.mode == "lowrank":
        replicated["omega"] = lr.make_test_matrix(
            Y.shape[-1] * Y.shape[-2], cfg.rank)
    bundle = Bundle.create(data, mesh=mesh, replicated=replicated)
    return bundle, {"tau": tau, "sig": sig}


@jax.named_scope("condat")
def _sparse_update(d, rep, cfg: SolverConfig):
    """Steps 7-8 (sparse): primal + dual updates, no cost.  Returns the
    new data blocks plus the scale-major (W, CX_new) the objective
    reuses — the iteration's single starlet forward serves both."""
    U = jnp.swapaxes(d["Xd"], 0, 1)               # (J, n_loc, S, S)
    W = jnp.swapaxes(d["W"], 0, 1)
    CX = jnp.swapaxes(d["CX"], 0, 1)
    U_adj = sparse_dual_adjoint(U, cfg.n_scales)
    grad = grad_from_HX(d["HX"], d["Y"], d["psf_fp"])
    X_new = primal_update(d["Xp"], U_adj, grad, rep["tau"])
    CX_new = starlet_batch.forward(X_new, cfg.n_scales)
    U_new = sparse_dual_update(U, CX_new, CX, W, rep["sig"])
    return dict(d, Xp=X_new, Xd=jnp.swapaxes(U_new, 0, 1),
                CX=jnp.swapaxes(CX_new, 0, 1),
                HX=psf_op.H_fp(X_new, d["psf_fp"])), (W, CX_new)


@jax.named_scope("condat")
def _lowrank_update(d, rep, axes, cfg: SolverConfig):
    """Steps 7-8 (low-rank): primal update + distributed randomized SVT."""
    U, sig = d["Xd"], rep["sig"]
    grad = grad_from_HX(d["HX"], d["Y"], d["psf_fp"])
    X_new, X_bar = condat_primal(d["Xp"], U, grad, rep["tau"],
                                 with_xbar=True)
    V = U + sig * X_bar
    flat = (V / sig).reshape(V.shape[0], -1)
    svt_flat = lr.randomized_svt_local(
        flat, rep["omega"], cfg.lam / sig, axes=axes or None)
    U_new = V - sig * svt_flat.reshape(V.shape)
    return dict(d, Xp=X_new, Xd=U_new,
                HX=psf_op.H_fp(X_new, d["psf_fp"]))


def make_step_fn(cfg: SolverConfig):
    """The per-partition iteration (steps 7-9): identical math to the
    sequential solver; ``axes`` carries the psum targets."""

    def step(d, rep, axes):
        if cfg.mode == "sparse":
            d_new, (W, CX_new) = _sparse_update(d, rep, cfg)
            with jax.named_scope("objective"):
                cost_part = data_cost_from(d_new["HX"], d["Y"]) + \
                    sparse_reg_cost(CX_new, W)
                if axes:
                    cost_part = jax.lax.psum(cost_part, axes)
            return d_new, {"cost": cost_part}
        d_new = _lowrank_update(d, rep, axes, cfg)
        with jax.named_scope("objective"):
            # nuclear-norm cost via the same range finder
            nuc = lr.nuclear_norm_rf(
                d_new["Xp"].reshape(d_new["Xp"].shape[0], -1),
                rep["omega"], axes)
            cost_part = data_cost_from(d_new["HX"], d["Y"])
            if axes:
                cost_part = jax.lax.psum(cost_part, axes)
            cost_part = cost_part + cfg.lam * nuc
        return d_new, {"cost": cost_part}

    return step


def make_light_step_fn(cfg: SolverConfig):
    """The same iteration without the objective evaluation — the
    ``cost_every`` fast path (skips a full starlet forward + PSF
    convolution per record in sparse mode, a Gram eigendecomposition in
    low-rank mode)."""

    def step(d, rep, axes):
        if cfg.mode == "sparse":
            d_new, _ = _sparse_update(d, rep, cfg)
            return d_new
        return _lowrank_update(d, rep, axes, cfg)

    return step


def make_cost_fn(cfg: SolverConfig):
    """Standalone objective over the post-iteration state — the
    ``cost_every="chunk"`` mode (``engine.make_chunk_cost_step``): the
    scan body runs only the cost-free step and this evaluates once per
    dispatch, off the carried forward model ``HX``."""

    @jax.named_scope("objective")
    def cost(d, rep, axes):
        data_part = data_cost_from(d["HX"], d["Y"])
        if cfg.mode == "sparse":
            # the carried CX IS Phi(Xp): the per-chunk objective is a
            # weighted reduction with no transform at all
            reg = sparse_reg_cost(jnp.swapaxes(d["CX"], 0, 1),
                                  jnp.swapaxes(d["W"], 0, 1))
            total = data_part + reg
            if axes:
                total = jax.lax.psum(total, axes)
            return {"cost": total}
        if axes:
            data_part = jax.lax.psum(data_part, axes)
        nuc = lr.nuclear_norm_rf(d["Xp"].reshape(d["Xp"].shape[0], -1),
                                 rep["omega"], axes)
        return {"cost": data_part + cfg.lam * nuc}

    return cost


@register("deconvolve")
class DeconvolutionProblem(Problem):
    """Algorithm 1, declared once (DESIGN.md §14).

    ``cfg.mode`` selects the regulariser: ``"sparse"`` (starlet + noise-
    adaptive weights) or ``"lowrank"`` (distributed randomized SVT).
    The broadcast state (step sizes, SVT test matrix) is constant across
    iterations, so there is no ``refresh_replicated`` and the light step
    returns bare data (``replicated_in_carry`` stays False).
    """

    def __init__(self, cfg: Optional[SolverConfig] = None,
                 sigma_noise: float = 0.02):
        self.cfg = cfg if cfg is not None else SolverConfig()
        self.sigma_noise = sigma_noise
        self._step = make_step_fn(self.cfg)
        self._light = make_light_step_fn(self.cfg)
        self._cost = make_cost_fn(self.cfg)

    def init_bundle(self, inputs, mesh) -> Bundle:
        Y, psfs = inputs
        bundle, _ = build_bundle(Y, psfs, self.cfg, mesh=mesh,
                                 sigma_noise=self.sigma_noise)
        return bundle

    def full_step(self, d, rep, axes):
        return self._step(d, rep, axes)

    def light_step(self, d, rep, axes):
        return self._light(d, rep, axes)

    def cost(self, d, rep, axes):
        return self._cost(d, rep, axes)

    def finalize(self, bundle, log):
        return gather(bundle)["Xp"], {}

    def batch_axes(self):
        from repro.core.batching import BatchAxes
        # (Y, psfs) are both stamp-major; every bundle leaf (including
        # the paired PSF spectra and the starlet weights) is fully
        # per-record, so zero-padded stamps are inert.  The SVT test
        # matrix depends only on config and is shared across a bucket;
        # the noise level is a constructor scalar shared by declaration.
        shared = ("omega",) if self.cfg.mode == "lowrank" else ()
        return BatchAxes(record_axes=(0, 0), shared_in_batch=shared,
                         instance_invariant=("sigma_noise",))


def deconvolve(Y, psfs, cfg: SolverConfig, mesh=None,
               sigma_noise: float = 0.02,
               max_iter: Optional[int] = None,
               tol: Optional[float] = None,
               chunk: int = 8, cost_every: int = 1):
    """End-to-end Algorithm 1. Returns (X*, driver log).

    .. deprecated:: PR 4
        Thin shim over ``solve(DeconvolutionProblem(cfg), Y, psfs)``
        (bit-identical wiring); use the ``solve()`` entry point.
    """
    warnings.warn(
        "deconvolve(...) is deprecated; use repro.core.problem.solve("
        '"deconvolve", Y, psfs, cfg=cfg, ...) (DESIGN.md §14)',
        DeprecationWarning, stacklevel=2)
    sol = solve(DeconvolutionProblem(cfg, sigma_noise=sigma_noise),
                Y, psfs, mesh=mesh, max_iter=max_iter,
                tol=tol, chunk=chunk, cost_every=cost_every)
    return sol.x, sol.log
