"""Public wrappers for the fused Condat elementwise tails.

``use_kernel=None`` auto-selects: the Pallas kernel where it compiles to
Mosaic (TPU), the pure-jnp oracle elsewhere — on CPU/GPU hosts the
oracle already collapses to one fused XLA loop per pass, and the
interpreter would only add overhead inside the solver scan.  Tests pass
``use_kernel=True`` to exercise the kernel in interpreter mode on any
backend.

The kernel path routes through ``kernels.common.degraded_call``: a
Pallas construction failure (or injected ``kernel`` chaos fault)
degrades the ``condat_elwise`` family compiled → interpret → ref once
per process with a recorded warning (DESIGN.md §18).  Selection happens
at Python level; both implementations underneath stay jitted.

Both wrappers accept arbitrary leading batch shape: ``condat_dual``
flattens the (scale, record) leading axes of the dual stack into the
kernel's 1-D grid axis (the weight column broadcasts per leading index,
shaped (..., 1, 1) like ``condat.weight_matrix`` emits).  The kernels
run on the flattened arrays at their true length (a ragged last block)
and write X_new / the clamped U into the first operand's buffer, so
nothing is padded in or sliced out.  These jitted wrappers donate
nothing: a caller's array stays unchanged, and inside a solver's chunk
program the update reuses the carried buffer.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import auto_interpret, degraded_call
from repro.kernels.condat_elwise.kernel import (condat_dual_fwd,
                                                condat_primal_fwd)
from repro.kernels.condat_elwise.ref import (condat_dual_ref,
                                             condat_primal_ref)

FAMILY = "condat_elwise"


@partial(jax.jit, static_argnames=("with_xbar", "block_n", "interpret"))
def _primal_kernel(X, U_adj, grad, tau, *, with_xbar: bool,
                   block_n, interpret: bool):
    lead = X.shape[:-2]
    flat = (-1,) + X.shape[-2:]
    out = condat_primal_fwd(X.reshape(flat), U_adj.reshape(flat),
                            grad.reshape(flat), tau, with_xbar=with_xbar,
                            block_n=block_n, interpret=interpret)
    if with_xbar:
        return (out[0].reshape(lead + X.shape[-2:]),
                out[1].reshape(lead + X.shape[-2:]))
    return out.reshape(lead + X.shape[-2:])


@partial(jax.jit, static_argnames=("with_xbar",))
def _primal_ref(X, U_adj, grad, tau, *, with_xbar: bool):
    return condat_primal_ref(X, U_adj, grad, tau, with_xbar=with_xbar)


def condat_primal(X, U_adj, grad, tau, *, with_xbar: bool = False,
                  use_kernel=None, block_n=None, interpret=None):
    if use_kernel is None:
        use_kernel = not auto_interpret()
    if not use_kernel:
        return _primal_ref(X, U_adj, grad, tau, with_xbar=with_xbar)
    return degraded_call(
        FAMILY,
        kernel=lambda interp: _primal_kernel(
            X, U_adj, grad, tau, with_xbar=with_xbar, block_n=block_n,
            interpret=interp),
        ref=lambda: _primal_ref(X, U_adj, grad, tau, with_xbar=with_xbar),
        requested_interpret=interpret)


@partial(jax.jit, static_argnames=("block_m", "interpret"))
def _dual_kernel(U, C_new, C_old, W, sig, *, block_m, interpret: bool):
    lead = U.shape[:-2]
    flat = (-1,) + U.shape[-2:]
    w = jnp.broadcast_to(W, lead + (1, 1)).reshape((-1, 1, 1))
    out = condat_dual_fwd(U.reshape(flat), C_new.reshape(flat),
                          C_old.reshape(flat), w, sig,
                          block_m=block_m, interpret=interpret)
    return out.reshape(U.shape)


_dual_ref = jax.jit(condat_dual_ref)


def condat_dual(U, C_new, C_old, W, sig, *, use_kernel=None,
                block_m=None, interpret=None):
    if use_kernel is None:
        use_kernel = not auto_interpret()
    if not use_kernel:
        return _dual_ref(U, C_new, C_old, W, sig)
    return degraded_call(
        FAMILY,
        kernel=lambda interp: _dual_kernel(U, C_new, C_old, W, sig,
                                           block_m=block_m,
                                           interpret=interp),
        ref=lambda: _dual_ref(U, C_new, C_old, W, sig),
        requested_interpret=interpret)
