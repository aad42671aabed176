"""The benchmark's generators and operator against the program's
generators and float64 numpy, at small sizes on the CPU."""
import sys
from pathlib import Path

import jax
import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import dft  # noqa: E402
import generate  # noqa: E402


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / np.abs(b).max()


STAMPS = cells.component("generators", "stamps")


def test_stamps_match_the_programs_simulation():
    from repro.imaging import psf
    key = jax.random.PRNGKey(11)
    Y, X, P = STAMPS.stamps(key, n=12, stamp=41, sigma=0.02)
    ref = psf.simulate(12, key)
    assert _rel(X, ref.X_true) < 1e-6
    assert _rel(P, ref.psfs) < 1e-6
    assert _rel(Y, ref.Y) < 1e-6


def test_configuration_names_its_generator():
    config = {"inputs": "stamps", "sizes": {"stamps": 5, "stamp": 41},
              "generator": {"sigma": 0.02}}
    key = generate.seed_key(3)
    Y, P = generate.make(config, key)
    Y0, _, P0 = STAMPS.stamps(key, n=5, stamp=41, sigma=0.02)
    assert np.array_equal(Y, Y0) and np.array_equal(P, P0)


def test_operator_and_adjoint_match_float64_numpy():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 9, 9))
    k = rng.random((3, 9, 9))
    spec = dft.spectra(np.float32(k), "highest")
    got = dft.convolve(np.float32(x), spec, "highest")
    # 'same' linear convolution with the kernel centred at (4, 4)
    ref = np.zeros_like(x)
    for m in range(9):
        for n in range(9):
            for a in range(9):
                for b in range(9):
                    u, v = m - a + 4, n - b + 4
                    if 0 <= u < 9 and 0 <= v < 9:
                        ref[:, m, n] += x[:, a, b] * k[:, u, v]
    assert _rel(got, ref) < 1e-5
    y = rng.standard_normal((3, 9, 9))
    adj = dft.convolve(np.float32(y), spec, "highest", adjoint=True)
    lhs = np.sum(np.asarray(got, np.float64) * y)
    rhs = np.sum(x * np.asarray(adj, np.float64))
    assert abs(lhs - rhs) < 1e-4 * abs(lhs)
    low = dft.convolve(np.float32(x), dft.spectra(np.float32(k),
                                                  "bfloat16"), "bfloat16")
    assert _rel(low, ref) > 1e-4


def test_seed_key_takes_seeds_beyond_32_bits():
    a = generate.seed_key(2 ** 33 + 5)
    b = generate.seed_key(5)
    assert not np.array_equal(np.asarray(jax.random.key_data(a)),
                              np.asarray(jax.random.key_data(b)))
