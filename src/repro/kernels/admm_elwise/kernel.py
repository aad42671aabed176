"""Fused SCDL ADMM elementwise tail — Pallas TPU kernel.

After the W ridge solves, Algorithm 2's step 8 finishes with a
soft-threshold of each splitting variable and three dual ascent updates.
As separate jnp ops that is ~5 full HBM passes over five (K_loc, A)
arrays per iteration; at the GS shape (K=40k, A=512) each array is
~80 MB, so the chain is purely HBM-bound.  The fused kernel streams one
(block_k, 5, A) tile of the stacked multiplier state ``YZ = [Y1, Y2,
Y3, Z1, Z2]`` plus the two fresh code tiles through VMEM and writes the
updated stack in the same pass — one read + one write per array total.
The splitting variables P/Q stay VMEM-internal; the Z planes are the
pre-folded right-hand-side terms the next W solves consume (see
``ref.py`` for the algebra).

Grid: (K / block_k,) over the sample axis, embarrassingly parallel
(dimension_semantics: parallel); every program touches disjoint rows.
The ADMM constants (c1, c2, c3 and the thresholds t1 = lam_h/c1,
t2 = lam_l/c2) are static configuration, baked into the kernel body.
``block_k`` is derived from A against the scoped-VMEM budget
(``kernels.common.vmem_rows``): a (5, A) row of the stack pads to an
(8, A) tile, and under ``vmap`` (``solve_many``) Mosaic keeps about six
such rows live per sample, so A = 512 takes 128 samples (256 overflowed
16 MiB by 7 MiB).  Sample counts that don't divide ``block_k`` zero-pad
up to a whole block (pad rows produce pad rows; the caller slices them
off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import auto_interpret, pad_leading, vmem_rows

_LIVE_BLOCKS = 6


def _admm_kernel(wh_ref, wl_ref, yz_ref, out_ref, *, c1, c2, c3, t1, t2):
    wh = wh_ref[...].astype(jnp.float32)
    wl = wl_ref[...].astype(jnp.float32)
    yz = yz_ref[...].astype(jnp.float32)                # (bk, 5, A)
    y1, y2, y3 = yz[:, 0], yz[:, 1], yz[:, 2]

    # soft(V, t) = V - clip(V) collapses the dual step to a clamp:
    # Y' = Y + c (soft(V) - W) = -c clip(V), c P = (c W - Y) + Y'
    y1n = -c1 * jnp.clip(wh - y1 / c1, -t1, t1)
    y2n = -c2 * jnp.clip(wl - y2 / c2, -t2, t2)
    y3n = y3 + c3 * (wh - wl)
    z1 = (c1 * wh - y1) + 2.0 * y1n - y3n + c3 * wl
    z2 = (c2 * wl - y2) + 2.0 * y2n + y3n
    out_ref[...] = jnp.stack([y1n, y2n, y3n, z1, z2],
                             axis=1).astype(out_ref.dtype)


def admm_elwise_fwd(Wh, Wl, YZ, *, c1, c2, c3, t1, t2,
                    block_k=None, interpret=None):
    """Wh/Wl: (K, A); YZ: (K, 5, A).  Returns the updated (K, 5, A)."""
    if interpret is None:
        interpret = auto_interpret()
    K, A = Wh.shape
    if block_k is None:
        block_k = vmem_rows((5, A), _LIVE_BLOCKS, cap=256)
    block_k = min(block_k, K)
    ins, k_full = pad_leading([Wh, Wl, YZ], block_k)
    pad = k_full - K

    kernel = functools.partial(_admm_kernel, c1=c1, c2=c2, c3=c3,
                               t1=t1, t2=t2)
    out = pl.pallas_call(
        kernel,
        grid=(k_full // block_k,),
        in_specs=[
            pl.BlockSpec((block_k, A), lambda i: (i, 0)),
            pl.BlockSpec((block_k, A), lambda i: (i, 0)),
            pl.BlockSpec((block_k, 5, A), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_k, 5, A), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k_full, 5, A), YZ.dtype),
        interpret=interpret,
        name="admm_elwise",
    )(*ins)
    return out[:K] if pad else out
