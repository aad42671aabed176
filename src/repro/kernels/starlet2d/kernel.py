"""Batched starlet (a-trous B3) smoothing — Pallas TPU kernel.

The PSF use case applies Phi / Phi^T to every 41x41 stamp every
iteration: 2 x n_scales x 10k+ small separable convolutions — the
compute hotspot of the paper's sparse solver.  Each program holds
``block_n`` stamps as one VMEM-resident (block_n, H, W) block and runs
the 5-tap correlation along both image axes as shifted multiply-adds,
with no HBM round-trip between the two separable passes.  The image
width sits in the lanes, so a 41x41 stamp occupies a padded 48x128
tile.

Mosaic keeps about ten such blocks live for the kernel body (input,
accumulator, the slices and concatenations of each shift), so
``block_n`` is derived from the stamp size against the scoped-VMEM
budget (``kernels.common.vmem_rows``): 48 stamps at 41x41.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import auto_interpret, vmem_rows

_TAPS = ((0, 1.0 / 16), (1, 4.0 / 16), (2, 6.0 / 16), (3, 4.0 / 16),
         (4, 1.0 / 16))
_LIVE_BLOCKS = 10


def _starlet_kernel(x_ref, o_ref, *, step, height, width):
    x = x_ref[...].astype(jnp.float32)                  # (bn, H, W)

    def pass_axis(arr, axis, size):
        acc = jnp.zeros_like(arr)
        for t, w in _TAPS:
            # a whole-period shift (the centre tap, or a hole as wide as
            # the stamp) is the identity; rolling by it would lower to
            # a zero-width slice, which Mosaic refuses
            shift = (2 - t) * step % size
            acc = acc + w * (jnp.roll(arr, shift, axis=axis) if shift
                             else arr)
        return acc

    y = pass_axis(x, 2, width)
    y = pass_axis(y, 1, height)
    o_ref[...] = y.astype(o_ref.dtype)


def smooth_fwd(imgs, scale: int, *, block_n=None, interpret=None):
    """imgs: (N, H, W) float; one B3 smoothing at dyadic ``scale``.

    Arbitrary batch sizes are supported: the stamp batch is padded up to
    a whole number of ``block_n`` blocks (the smoothing is per-stamp, so
    pad stamps never contaminate real ones) and the result sliced back.
    On TPU the full block is always kept so every program sees the same
    block shape; in interpreter mode (no alignment constraint) the
    batch collapses to a single block when padding would cost more than
    half a block, so the pad-and-slice path still runs — and is CI-
    covered — for moderate misalignment without pathological waste.
    ``block_n=None`` derives the block from the stamp size.
    """
    if interpret is None:
        interpret = auto_interpret()
    N, H, W = imgs.shape
    if block_n is None:
        block_n = vmem_rows((H, W), _LIVE_BLOCKS)
    block_n = min(block_n, N) if interpret else block_n
    if interpret and (-N % block_n) > block_n // 2:
        block_n = N
    n_pad = -N % block_n
    if n_pad:
        imgs = jnp.concatenate(
            [imgs, jnp.zeros((n_pad,) + imgs.shape[1:], imgs.dtype)])
    n_full = N + n_pad
    kernel = functools.partial(_starlet_kernel, step=1 << scale,
                               height=H, width=W)
    out = pl.pallas_call(
        kernel,
        grid=(n_full // block_n,),
        in_specs=[pl.BlockSpec((block_n, H, W), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((block_n, H, W), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_full, H, W), imgs.dtype),
        interpret=interpret,
        name="starlet2d_smooth",
    )(imgs)
    return out[:N] if n_pad else out
