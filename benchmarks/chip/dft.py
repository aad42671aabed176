"""'Same' convolution of each stamp with its own centred PSF, as dense
DFT products on a zero-padded grid: the benchmark's own operator, used
by the stamp generator and by the deconvolution reference.  It shares
no code with the program's PSF operator and calls no FFT (XLA:TPU's FFT
was wrong at large stamp counts).

``prec`` is the precision of every product, here and in the
references: ``"highest"`` (float32 operands, full float32 precision)
for the reference, ``"bfloat16"`` (operands rounded to bfloat16, float32
accumulation: one MXU pass) for its control."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "bfloat16")


def einsum(spec: str, a, b, prec: str):
    """``jnp.einsum`` of two operands at the stated precision."""
    if prec == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if prec == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {prec!r}; have {PRECISIONS}")


def pad_for(s: int) -> int:
    """A grid on which circular convolution of two s x s arrays equals
    linear convolution: at least 2 s - 1."""
    return 2 * s - 1


def bases(s: int, pad: int, shift: int = 0):
    """cos and sin of 2 pi k (n - shift) / pad for k < pad, n < s."""
    k = np.arange(pad)[:, None]
    n = np.arange(s)[None, :] - shift
    ang = 2.0 * np.pi * ((k * n) % pad) / pad
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _fwd(x, c, s_, prec):
    """(re, im) of C x C^T for C = cos - i sin, x real (..., s, s)."""
    ar = einsum("ka,...ab->...kb", c, x, prec)
    ai = -einsum("ka,...ab->...kb", s_, x, prec)
    br = (einsum("...kb,lb->...kl", ar, c, prec)
          + einsum("...kb,lb->...kl", ai, s_, prec))
    bi = (einsum("...kb,lb->...kl", ai, c, prec)
          - einsum("...kb,lb->...kl", ar, s_, prec))
    return br, bi


def spectra(psfs, prec):
    """DFT of each PSF with its centre moved to the grid's origin."""
    s = psfs.shape[-1]
    c, s_ = bases(s, pad_for(s), shift=s // 2)
    return _fwd(psfs, c, s_, prec)


def convolve(x, spec, prec, adjoint: bool = False):
    """H x (or H^T x with ``adjoint``) for stamps x (..., s, s) and the
    PSF spectra ``spec`` from :func:`spectra`."""
    s = x.shape[-1]
    pad = pad_for(s)
    c, s_ = bases(s, pad)
    kr, ki = spec
    if adjoint:
        ki = -ki
    br, bi = _fwd(x, c, s_, prec)
    pr, pi = br * kr - bi * ki, br * ki + bi * kr
    g, h = c.T, s_.T                                  # (s, pad)
    qr = (einsum("mk,...kl->...ml", g, pr, prec)
          - einsum("mk,...kl->...ml", h, pi, prec))
    qi = (einsum("mk,...kl->...ml", g, pi, prec)
          + einsum("mk,...kl->...ml", h, pr, prec))
    out = (einsum("...ml,nl->...mn", qr, g, prec)
           - einsum("...ml,nl->...mn", qi, h, prec))
    return out / (pad * pad)
