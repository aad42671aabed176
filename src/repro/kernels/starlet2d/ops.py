"""Public wrappers for the starlet-smoothing kernel, plus the full
batched transforms built from it.

``forward`` / ``adjoint`` are the batched counterparts of
``repro.imaging.starlet.forward``/``adjoint`` operating on a whole
(N, H, W) stamp stack at once — the layout the Condat solver's dual
updates use every iteration.  The adjoint shares cumulative smoothing
products across scales (Horner evaluation, 2J - 1 kernel launches
instead of O(J^2)).

The kernel path routes through ``kernels.common.degraded_call``, so a
Pallas failure degrades the ``starlet2d`` family compiled → interpret
→ ref once per process with a recorded warning (DESIGN.md §18)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import degraded_call
from repro.kernels.starlet2d.kernel import smooth_fwd
from repro.kernels.starlet2d.ref import smooth_ref

FAMILY = "starlet2d"


@partial(jax.jit, static_argnames=("scale", "block_n", "interpret"))
def _smooth_kernel(imgs, *, scale: int, block_n, interpret: bool):
    return smooth_fwd(imgs, scale, block_n=block_n, interpret=interpret)


@partial(jax.jit, static_argnames=("scale",))
def _smooth_ref(imgs, *, scale: int):
    return smooth_ref(imgs, scale)


def smooth(imgs, *, scale: int, use_kernel: bool = True,
           block_n=None, interpret=None):
    if not use_kernel:
        return _smooth_ref(imgs, scale=scale)
    return degraded_call(
        FAMILY,
        kernel=lambda interp: _smooth_kernel(imgs, scale=scale,
                                             block_n=block_n,
                                             interpret=interp),
        ref=lambda: _smooth_ref(imgs, scale=scale),
        requested_interpret=interpret)


@jax.named_scope("starlet")
def decompose(imgs, n_scales: int, **kw):
    """Batched starlet analysis via the kernel: (N,H,W) -> (J+1,N,H,W)."""
    scales = []
    c = imgs
    for j in range(n_scales):
        c_next = smooth(c, scale=j, **kw)
        scales.append(c - c_next)
        c = c_next
    scales.append(c)
    return jnp.stack(scales)


@jax.named_scope("starlet")
def forward(imgs, n_scales: int, **kw):
    """Batched Phi: detail scales only, (N,H,W) -> (J,N,H,W)."""
    return decompose(imgs, n_scales, **kw)[:-1]


@jax.named_scope("starlet")
def adjoint(coeffs, n_scales: int, **kw):
    """Batched Phi^T: (J,N,H,W) -> (N,H,W).

    Horner evaluation of the cascade transpose (see
    ``repro.imaging.starlet.adjoint``): v_j = (I - H_j) w_j, then
    acc_j = v_j + H_j acc_{j+1} from the finest carried scale down.
    """
    acc = coeffs[n_scales - 1] - smooth(coeffs[n_scales - 1],
                                        scale=n_scales - 1, **kw)
    for j in range(n_scales - 2, -1, -1):
        v = coeffs[j] - smooth(coeffs[j], scale=j, **kw)
        acc = v + smooth(acc, scale=j, **kw)
    return acc
