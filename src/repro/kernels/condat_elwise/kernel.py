"""Fused Condat primal/dual elementwise tails — Pallas TPU kernels.

Two grid passes per iteration (the starlet forward between them is a
hard data dependency — see ``ref.py``):

- ``condat_primal_fwd``: one (block_n, S, S) tile of X, Phi^T U and the
  gradient streams through VMEM and writes the fresh primal (and, for
  the low-rank path, the over-relaxed X_bar from the same read) — one
  read of each operand, one write per output, vs the seed's ~3
  separately-rooted elementwise fusions.
- ``condat_dual_fwd``: one (block_m, S, S) tile of the dual stack U and
  the two starlet coefficient stacks, plus the matching (block_m, 1, 1)
  noise-weight column, fused over-relaxation + clamp in a single pass
  over the (J x n)-times-larger dual state.

The step sizes tau/sig are *traced* scalars (they live in the bundle's
replicated state), so they enter through SMEM rather than being baked
into the kernel body like ``admm_elwise``'s static ADMM constants.

Grids are 1-D over the flattened leading (record/scale) axis,
embarrassingly parallel, and run on the caller's arrays at their true
length: ``pl.cdiv(rows, block)`` steps, the last block ragged where the
block does not divide the rows.  Each row's output depends only on that
row, so the rows a ragged block reads past the end (undefined) only
produce writes that Pallas drops.  Each pass updates its first array
operand in place (X becomes X_new, U the clamped U): no padded copy in,
no sliced copy out.  Where the caller still holds that operand, XLA
copies it first, so the alias never changes a live array.
Block sizes default to what the scoped-VMEM budget allows for the
stamp size (``kernels.common.vmem_rows``): a 41x41 stamp pads to a
48x128 tile, and inside a solver's chunk program Mosaic keeps about ten
fp32 blocks live for either pass (operands, temporaries, outputs), so
both take 48 rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import auto_interpret, vmem_rows

# fp32 blocks each kernel body keeps live in VMEM (see vmem_rows)
_LIVE_BLOCKS = 10


def _primal_kernel(tau_ref, x_ref, ua_ref, g_ref, xn_ref):
    t = tau_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    xn = jnp.maximum(x - t * g_ref[...].astype(jnp.float32)
                     - t * ua_ref[...].astype(jnp.float32), 0.0)
    xn_ref[...] = xn.astype(xn_ref.dtype)


def _primal_xbar_kernel(tau_ref, x_ref, ua_ref, g_ref, xn_ref, xb_ref):
    t = tau_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    xn = jnp.maximum(x - t * g_ref[...].astype(jnp.float32)
                     - t * ua_ref[...].astype(jnp.float32), 0.0)
    xn_ref[...] = xn.astype(xn_ref.dtype)
    xb_ref[...] = (2.0 * xn - x).astype(xb_ref.dtype)


def _dual_kernel(sig_ref, u_ref, cn_ref, co_ref, w_ref, out_ref):
    s = sig_ref[0, 0]
    v = u_ref[...].astype(jnp.float32) + \
        s * (2.0 * cn_ref[...].astype(jnp.float32)
             - co_ref[...].astype(jnp.float32))
    w = w_ref[...].astype(jnp.float32)                # (bm, 1, 1)
    out_ref[...] = jnp.clip(v, -w, w).astype(out_ref.dtype)


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def condat_primal_fwd(X, U_adj, grad, tau, *, with_xbar: bool = False,
                      block_n=None, interpret=None):
    """X/U_adj/grad: (N, S, S); tau scalar.  Returns X_new (and X_bar);
    X_new takes X's buffer."""
    if interpret is None:
        interpret = auto_interpret()
    n, s = X.shape[0], X.shape[-1]
    if block_n is None:
        block_n = vmem_rows((s, s), _LIVE_BLOCKS)
    block_n = min(block_n, n)
    tau = jnp.asarray(tau, jnp.float32).reshape((1, 1))

    blk = pl.BlockSpec((block_n, s, s), lambda i: (i, 0, 0))
    shape = jax.ShapeDtypeStruct(X.shape, X.dtype)
    kernel = _primal_xbar_kernel if with_xbar else _primal_kernel
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, block_n),),
        in_specs=[_scalar_spec(), blk, blk, blk],
        out_specs=[blk, blk] if with_xbar else blk,
        out_shape=[shape, shape] if with_xbar else shape,
        input_output_aliases={1: 0},
        interpret=interpret,
        name="condat_elwise_primal",
    )(tau, X, U_adj, grad)


def condat_dual_fwd(U, C_new, C_old, W, sig, *, block_m=None,
                    interpret=None):
    """U/C_new/C_old: (M, S, S); W: (M, 1, 1); sig scalar.  The clamped
    dual takes U's buffer."""
    if interpret is None:
        interpret = auto_interpret()
    m, s = U.shape[0], U.shape[-1]
    if block_m is None:
        block_m = vmem_rows((s, s), _LIVE_BLOCKS)
    block_m = min(block_m, m)
    sig = jnp.asarray(sig, jnp.float32).reshape((1, 1))

    blk = pl.BlockSpec((block_m, s, s), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _dual_kernel,
        grid=(pl.cdiv(m, block_m),),
        in_specs=[_scalar_spec(), blk, blk, blk,
                  pl.BlockSpec((block_m, 1, 1), lambda i: (i, 0, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(U.shape, U.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="condat_elwise_dual",
    )(sig, U, C_new, C_old, W)
