"""Survey stamps: the same galaxies, PSFs and noise as the program's
``repro.imaging.psf.simulate`` draws for the same key (Euclid-like
stand-ins for the survey's stamps: two-component elliptical galaxies,
anisotropic Gaussian PSFs whose ellipticity varies smoothly over the
field), blurred with the benchmark's own operator (``dft.py``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import dft


def _gaussian2d(n, cx, cy, sx, sy, theta):
    yy, xx = jnp.mgrid[0:n, 0:n]
    xr = (xx - cx) * jnp.cos(theta) + (yy - cy) * jnp.sin(theta)
    yr = -(xx - cx) * jnp.sin(theta) + (yy - cy) * jnp.cos(theta)
    return jnp.exp(-0.5 * ((xr / sx) ** 2 + (yr / sy) ** 2))


@partial(jax.jit, static_argnames=("n", "stamp"))
def stamps(key, *, n: int, stamp: int, sigma: float):
    """(Y, X_true, psfs), each (n, stamp, stamp) float32."""
    kg, _, kn, kpos = jax.random.split(key, 4)
    c = stamp // 2

    def galaxy(u):
        a = _gaussian2d(stamp, c + 4 * (u[0] - .5), c + 4 * (u[1] - .5),
                        2.0 + 3.0 * u[2], 1.5 + 2.0 * u[3], jnp.pi * u[4])
        b = _gaussian2d(stamp, c, c, 1.0 + u[5], 1.0 + u[5], 0.0)
        img = a + 0.5 * b
        return img / jnp.sum(img)

    def psf(p):
        e = 0.15 * jnp.sin(2 * jnp.pi * p[0]) + 0.1 * p[1]
        k = _gaussian2d(stamp, c, c, 1.8 * (1 + e), 1.8 * (1 - e),
                        jnp.pi * (p[0] + p[1]))
        return k / jnp.sum(k)

    X = jax.vmap(galaxy)(jax.random.uniform(kg, (n, 6)))
    psfs = jax.vmap(psf)(jax.random.uniform(kpos, (n, 2)))
    blurred = dft.convolve(X, dft.spectra(psfs, "highest"), "highest")
    Y = blurred + sigma * jax.random.normal(kn, X.shape, jnp.float32)
    return Y, X, psfs


def make(config: dict, key):
    """(Y, psfs): what ``solve`` takes for a deconvolution deployment."""
    s = config["sizes"]
    Y, _, psfs = stamps(key, n=s["stamps"], stamp=s["stamp"],
                        sigma=config["generator"]["sigma"])
    return Y, psfs
