"""Space-variant PSF forward operator H and Euclid-like data simulation.

H(X) = [H^0 x^0, ..., H^n x^n]: every galaxy stamp is convolved with the
PSF at its own sky position (object-oriented deconvolution, paper §4.1).
Fourier-domain valid-centred convolution on padded grids; the adjoint
is correlation (conjugate in Fourier domain) — property-tested.  The
transforms are DFTs written as products with cos/sin bases
(:func:`rfft2_pad`, :func:`irfft2_crop`), not ``jnp.fft``: XLA:TPU's FFT
computed the operator wrong at large stamp counts.

Paired-FFT engine (DESIGN.md §16): the padded grid is the *smallest
fast FFT size >= 2S - 1* derived per stamp (the seed hardcoded 96 for
S = 41 — 18% stamp occupancy; the derived 81 = 3^4 cuts the FFT area
29%), the kernel spectra are carried as a precomputed ``(kf, conj kf)``
pair so the adjoint never conjugates on the hot path, and
:func:`conv_pair_f` runs one forward + one adjoint convolution of two
*independent* operands as ONE batched rfft2 -> one spectral multiply ->
one irfft2 (half the FFT launches of two separate calls).

The Great3/Euclid stamps and the 600 measured PSFs are not
redistributable offline; ``simulate`` generates matched-shape stand-ins:
Sersic-like galaxy blobs and anisotropic Gaussian PSFs whose ellipticity
varies smoothly across the field of view (the paper's "spatially varying
and anisotropic" property), plus white Gaussian noise.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.imaging.lowrank import matmul_fp32 as _mm

STAMP = 41


def fast_size(n: int) -> int:
    """Smallest 5-smooth integer >= n (pocketfft/XLA run radix-2/3/5
    plans; anything with a larger prime factor falls off the fast path)."""
    m = max(int(n), 1)
    while True:
        k = m
        for p in (2, 3, 5):
            while k % p == 0:
                k //= p
        if k == 1:
            return m
        m += 1


def pad_for(stamp: int, kernel: int = 0) -> int:
    """FFT grid for 'same' convolution of a (stamp, stamp) image with a
    (kernel, kernel) PSF: smallest fast size >= stamp + kernel - 1 (full
    linear-convolution support, so the cropped window is alias-free)."""
    kernel = kernel or stamp
    return fast_size(stamp + kernel - 1)


def _real(x: jax.Array) -> jax.Array:
    """FFT operand dtype: XLA's RFFT takes float32/float64 only, so
    half-precision stamps go through the engine in fp32 (results are
    cast back to the operand dtype by the callers)."""
    return x if jnp.issubdtype(x.dtype, jnp.floating) and \
        jnp.dtype(x.dtype).itemsize >= 4 else x.astype(jnp.float32)


# ------------------------------------------------ DFTs as matmuls
# The padded-grid transforms are DFTs written as dense products with
# cos/sin bases, at full fp32 precision.  On a TPU v5e, XLA's own FFT
# put the operator 25% off float64 at 5000 stamps and more (right at
# 2500 or fewer), so the convolution does not go through it (PERF.md).
# Only the s x s corner of the padded grid is ever nonzero on input or
# read on output, so every product has s (not pad) on one side.

def _basis(n_out: int, n_in: int, pad: int, shift: int = 0):
    """cos and sin of 2 pi k (n - shift) / pad, k < n_out, n < n_in."""
    k = np.arange(n_out)[:, None]
    n = np.arange(n_in)[None, :] - shift
    ang = 2.0 * np.pi * ((k * n) % pad) / pad
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def rfft2_pad(x: jax.Array, pad: int, shift: int = 0) -> jax.Array:
    """``jnp.fft.rfft2`` of the (pad, pad) grid that holds ``x`` (...,
    s, s) at rows and columns ``(n - shift) % pad`` and zeros elsewhere:
    (..., pad, pad // 2 + 1) complex.  Two products: the last axis
    against [cos; -sin], then axis -2 against the real form of the
    complex DFT matrix."""
    s = x.shape[-1]
    half = pad // 2 + 1
    c_h, s_h = _basis(half, s, pad, shift)
    c_f, s_f = _basis(pad, s, pad, shift)
    t = _mm(_real(x), jnp.concatenate([c_h, -s_h]).T)   # (..., s, 2 half)
    t = jnp.concatenate([t[..., :half], t[..., half:]], axis=-2)
    f = _mm(jnp.block([[c_f, s_f], [-s_f, c_f]]), t)    # (..., 2 pad, half)
    return jax.lax.complex(f[..., :pad, :], f[..., pad:, :])


def irfft2_crop(y: jax.Array, pad: int, s: int) -> jax.Array:
    """``jnp.fft.irfft2(y, s=(pad, pad))[..., :s, :s]`` for the half
    spectrum ``y`` (..., pad, pad // 2 + 1)."""
    half = y.shape[-1]
    c_f, s_f = _basis(s, pad, pad)
    z = _mm(jnp.block([[c_f, -s_f], [s_f, c_f]]),       # inverse, axis -2
            jnp.concatenate([jnp.real(y), jnp.imag(y)], axis=-2))
    z = jnp.concatenate([z[..., :s, :], z[..., s:, :]], axis=-1)
    # the half spectrum stands for its conjugate mirror: weight 2, but
    # 1 for the zero frequency and (even pad) the Nyquist one
    w = np.full(half, 2.0)
    w[0] = 1.0
    if pad % 2 == 0:
        w[-1] = 1.0
    c_h, s_h = _basis(s, half, pad)
    return _mm(z, jnp.concatenate([c_h * w, -s_h * w], axis=1).T) / (
        pad * pad)


def _fft_kernel(psf: jax.Array, pad: int) -> jax.Array:
    """Centered PSF -> rfft2 on the padded grid (kernel rolled to origin)."""
    return rfft2_pad(psf, pad, shift=psf.shape[-2] // 2)


def convolve(x: jax.Array, psf: jax.Array, adjoint: bool = False
             ) -> jax.Array:
    """'same' convolution of stamps with per-stamp PSFs.

    x: (..., S, S); psf: (..., S, S) broadcast-compatible leading dims.
    One-shot convenience API — loops should precompute :func:`psf_fft`
    (or :func:`psf_fft_pair`) instead of re-FFT'ing the kernel per call.
    """
    pad = pad_for(x.shape[-1], psf.shape[-2])
    return convolve_f(x, _fft_kernel(psf, pad), adjoint)


def H(X: jax.Array, psfs: jax.Array) -> jax.Array:
    """Forward operator over a stack: (n, S, S) x (n, S, S) -> (n, S, S)."""
    return convolve(X, psfs)


def Ht(Y: jax.Array, psfs: jax.Array) -> jax.Array:
    """Adjoint of :func:`H`."""
    return convolve(Y, psfs, adjoint=True)


# --------------------------------------------- cached-kernel variants
# The PSFs are constant across solver iterations, so their padded FFTs
# (1/3 of every convolution's FFT work) are computed once and carried in
# the bundle.  The pair layout additionally bakes in the conjugate so
# the per-iteration adjoint is a plain spectral multiply.

def psf_fft(psfs: jax.Array, pad: int = 0) -> jax.Array:
    """Precompute the padded rfft2 PSF kernels for :func:`H_f`/:func:`Ht_f`."""
    return _fft_kernel(psfs, pad or pad_for(psfs.shape[-1]))


def psf_fft_pair(psfs: jax.Array, pad: int = 0) -> jax.Array:
    """The ``(kf, conj kf)`` spectra stacked record-major —
    (n, 2, pad, pad // 2 + 1) complex — so the pair co-partitions with
    the records in the bundle.  ``[:, 0]`` drives H, ``[:, 1]`` drives
    Ht (no conjugation on the hot path)."""
    kf = psf_fft(psfs, pad)
    return jnp.stack([kf, jnp.conj(kf)], axis=-3)


def grid_of(kf: jax.Array) -> int:
    """Recover the (square) padded grid size from a kernel spectrum —
    the full-height axis of rfft2 output."""
    return kf.shape[-2]


def convolve_f(x: jax.Array, kf: jax.Array, adjoint: bool = False
               ) -> jax.Array:
    """Same as :func:`convolve` with the PSF kernel FFT precomputed."""
    s = x.shape[-1]
    pad = grid_of(kf)
    xf = rfft2_pad(x, pad)
    if adjoint:
        kf = jnp.conj(kf)
    return irfft2_crop(xf * kf, pad, s).astype(x.dtype)


def H_f(X: jax.Array, kf: jax.Array) -> jax.Array:
    return convolve_f(X, kf)


def Ht_f(Y: jax.Array, kf: jax.Array) -> jax.Array:
    return convolve_f(Y, kf, adjoint=True)


# ------------------------------------------------- paired convolution

@jax.named_scope("psf_operator")
def H_fp(X: jax.Array, kf_pair: jax.Array) -> jax.Array:
    """Forward convolution off the carried pair (no conj, no kernel FFT)."""
    return convolve_f(X, kf_pair[..., 0, :, :])


@jax.named_scope("psf_operator")
def Ht_fp(Y: jax.Array, kf_pair: jax.Array) -> jax.Array:
    """Adjoint convolution off the carried pair — the conjugate spectrum
    is precomputed, so this is one rfft2 -> multiply -> irfft2."""
    return convolve_f(Y, kf_pair[..., 1, :, :])


@jax.named_scope("psf_operator")
def conv_pair_f(A: jax.Array, B: jax.Array, kf_pair: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """(H(A), Ht(B)) for two independent operands in ONE batched FFT
    round trip: rfft2 of the stacked (n, 2, S, S) operand, one spectral
    multiply against the carried (kf, conj kf) pair, one irfft2 — half
    the kernel launches of calling H_f and Ht_f separately.

    Note the operands must be simultaneously available: inside the
    Condat iteration the forward input (the fresh primal) depends on the
    adjoint's output (the gradient), so the per-iteration pair there is
    a strict chain and stays two round trips (DESIGN.md §16).  Callers
    with genuinely independent operands — the augmented-operator power
    iteration in :func:`spectral_norm`, batched setup passes — get the
    full 2x launch saving.
    """
    s = A.shape[-1]
    pad = grid_of(kf_pair)
    z = jnp.stack([_real(A), _real(B)], axis=-3)     # (n, 2, S, S)
    out = irfft2_crop(rfft2_pad(z, pad) * kf_pair, pad, s)
    return out[..., 0, :, :].astype(A.dtype), \
        out[..., 1, :, :].astype(B.dtype)


def spectral_norm(psfs: jax.Array, iters: int = 60, key=None,
                  kf_pair: jax.Array = None) -> float:
    """||H||_2 via power iteration (the paper's solver needs it for the
    primal step size).

    Runs on the cached kernel spectra (the seed re-FFT'd the full PSF
    stack inside every iteration) and iterates the self-adjoint
    augmented operator A = [[0, Ht], [H, 0]] — A(u, v) = (Ht v, H u),
    whose spectral norm is exactly ||H||_2 — so each iteration is ONE
    :func:`conv_pair_f` round trip over two independent operands.  A
    contracts non-dominant modes at (sigma2/sigma1) per step vs the
    normal equations' square, hence the higher default ``iters`` (60
    paired round trips land a tighter estimate than the seed's 20
    normal-equation steps at half the kernel launches and none of the
    40 in-loop kernel FFTs).
    """
    if kf_pair is None:
        kf_pair = psf_fft_pair(psfs)
    key = key if key is not None else jax.random.PRNGKey(0)
    ku, kv = jax.random.split(key)
    u = jax.random.normal(ku, psfs.shape)
    v = jax.random.normal(kv, psfs.shape)
    # the whole iteration is one jitted program (module-level cache):
    # eagerly, lax.scan re-traces its closure body on every call, which
    # made this the dominant per-instance setup cost for populations.
    # Concurrent serve workers may race a cold call: jax's compilation
    # cache is internally locked, the function is pure, and its inputs
    # here are deterministic per (shape, key), so the worst case is one
    # duplicated compile, not a wrong value (regression-tested by
    # tests/test_serve.py::test_concurrent_setup_thread_safety).
    return float(_power_norm(u, v, kf_pair, iters))


@partial(jax.jit, static_argnames="iters")
def _power_norm(u, v, kf_pair, iters: int):
    nrm0 = jnp.sqrt(jnp.sum(u ** 2) + jnp.sum(v ** 2))
    u, v = u / nrm0, v / nrm0

    def body(carry, _):
        u, v = carry
        Hu, Htv = conv_pair_f(u, v, kf_pair)
        nrm = jnp.sqrt(jnp.sum(Htv ** 2) + jnp.sum(Hu ** 2)) + 1e-12
        return (Htv / nrm, Hu / nrm), nrm

    _, norms = jax.lax.scan(body, (u, v), None, length=iters)
    return norms[-1]


class PsfData(NamedTuple):
    Y: jax.Array          # noisy observed stamps   (n, S, S)
    X_true: jax.Array     # ground-truth stamps     (n, S, S)
    psfs: jax.Array       # per-object PSFs         (n, S, S)
    sigma: float          # noise std


def _gaussian2d(shape: Tuple[int, int], cx, cy, sx, sy, theta):
    yy, xx = jnp.mgrid[0:shape[0], 0:shape[1]]
    xr = (xx - cx) * jnp.cos(theta) + (yy - cy) * jnp.sin(theta)
    yr = -(xx - cx) * jnp.sin(theta) + (yy - cy) * jnp.cos(theta)
    return jnp.exp(-0.5 * ((xr / sx) ** 2 + (yr / sy) ** 2))


def simulate(n: int, key=None, stamp: int = STAMP, sigma: float = 0.02,
             dtype=jnp.float32) -> PsfData:
    """Euclid-like simulation: n stamps + spatially varying PSFs."""
    key = key if key is not None else jax.random.PRNGKey(42)
    kg, kp, kn, kpos = jax.random.split(key, 4)
    c = stamp // 2

    # galaxies: 2-component elliptical blobs with random orientation
    g1 = jax.random.uniform(kg, (n, 6))
    def galaxy(u):
        a = _gaussian2d((stamp, stamp), c + 4 * (u[0] - .5),
                        c + 4 * (u[1] - .5), 2.0 + 3.0 * u[2],
                        1.5 + 2.0 * u[3], jnp.pi * u[4])
        b = _gaussian2d((stamp, stamp), c, c, 1.0 + u[5], 1.0 + u[5], 0.0)
        img = a + 0.5 * b
        return img / jnp.sum(img)
    X = jax.vmap(galaxy)(g1).astype(dtype)

    # PSFs: anisotropy varies smoothly with a fake sky position
    pos = jax.random.uniform(kpos, (n, 2))
    def psf(p):
        e = 0.15 * jnp.sin(2 * jnp.pi * p[0]) + 0.1 * p[1]
        sx, sy = 1.8 * (1 + e), 1.8 * (1 - e)
        k = _gaussian2d((stamp, stamp), c, c, sx, sy,
                        jnp.pi * (p[0] + p[1]))
        return k / jnp.sum(k)
    psfs = jax.vmap(psf)(pos).astype(dtype)

    Y = H(X, psfs) + sigma * jax.random.normal(kn, X.shape, dtype)
    return PsfData(Y=Y, X_true=X, psfs=psfs, sigma=sigma)
