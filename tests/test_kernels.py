"""Per-kernel shape/dtype sweeps asserting allclose against the pure-jnp
oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------- starlet
from repro.kernels.starlet2d.ops import decompose as k_decompose
from repro.kernels.starlet2d.ops import smooth as k_smooth
from repro.kernels.starlet2d.ref import smooth_ref
from repro.imaging import starlet


@pytest.mark.parametrize("scale", [0, 1, 2, 3])
@pytest.mark.parametrize("shape", [(128, 41, 41), (256, 32, 32)])
def test_starlet_smooth(scale, shape):
    imgs = jax.random.normal(jax.random.fold_in(KEY, 11), shape)
    out = k_smooth(imgs, scale=scale)
    ref = smooth_ref(imgs, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_starlet_kernel_decompose_matches_imaging():
    imgs = jax.random.normal(jax.random.fold_in(KEY, 12), (128, 41, 41))
    co = k_decompose(imgs, 3)
    ref = jax.vmap(lambda im: starlet.decompose(im, 3),
                   in_axes=0, out_axes=1)(imgs)
    np.testing.assert_allclose(np.asarray(co), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("scale", [0, 2])
@pytest.mark.parametrize("shape", [(100, 41, 41), (37, 16, 16),
                                   (130, 41, 41)])
def test_starlet_smooth_non_block_aligned(scale, shape):
    """Batch sizes that don't divide block_n pad up and slice back."""
    imgs = jax.random.normal(jax.random.fold_in(KEY, 13), shape)
    out = k_smooth(imgs, scale=scale)
    ref = smooth_ref(imgs, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # a block size that forces padding must agree too
    out_pad = k_smooth(imgs, scale=scale, block_n=64)
    np.testing.assert_allclose(np.asarray(out_pad), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("scale", [3, 4])
def test_starlet_smooth_whole_period_holes(scale):
    """Holes as wide as the stamp (8 and 16 on a 16-wide stamp) shift
    taps by a whole period: the kernel uses them unrolled, as the
    centre tap, since rolling by 0 is a zero-width slice on TPU."""
    imgs = jax.random.normal(jax.random.fold_in(KEY, 14), (24, 16, 16))
    out = k_smooth(imgs, scale=scale)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(smooth_ref(imgs, scale)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tail", [(16, 16), (41, 41), (64, 64),
                                  (130, 130), (4, 41, 41)])
def test_vmem_rows_fits_the_scoped_budget(tail):
    from repro.kernels.common import SCOPED_VMEM_BYTES, vmem_rows
    rows = vmem_rows(tail, live=10)
    assert rows % 8 == 0 and 8 <= rows <= 128
    *lead, h, w = tail
    tile = int(np.prod(lead)) * (-(-h // 8) * 8) * (-(-w // 128) * 128) * 4
    if rows > 8:                                  # 8 is the floor
        assert rows * 10 * tile <= SCOPED_VMEM_BYTES * 3 // 4
        assert (rows + 8) * 10 * tile > SCOPED_VMEM_BYTES * 3 // 4 or \
            rows == 128


def test_vmem_rows_at_the_stamp_size():
    from repro.kernels.common import vmem_rows
    # a 41x41 stamp pads to a 48x128 fp32 tile (24 KiB)
    assert vmem_rows((41, 41), live=10) == 48


def test_starlet_batched_forward_adjoint_match_reference():
    """ops.forward/adjoint (the condat hot path) vs per-stamp vmap of the
    imaging reference, on a non-block-aligned batch."""
    from repro.kernels.starlet2d.ops import adjoint as k_adjoint
    from repro.kernels.starlet2d.ops import forward as k_forward
    imgs = jax.random.normal(jax.random.fold_in(KEY, 14), (100, 32, 32))
    co = k_forward(imgs, 4)
    ref = jax.vmap(lambda im: starlet.forward(im, 4),
                   in_axes=0, out_axes=1)(imgs)
    np.testing.assert_allclose(np.asarray(co), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    adj = k_adjoint(co, 4)
    ref_adj = jax.vmap(lambda u: starlet.adjoint(u, 4), in_axes=1)(co)
    np.testing.assert_allclose(np.asarray(adj), np.asarray(ref_adj),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------- dict outer
from repro.kernels.dict_outer.ops import dict_outer, dict_outer_pair
from repro.kernels.dict_outer.ref import dict_outer_pair_ref, dict_outer_ref

# (1000, ...) and block_k=512 exercise the non-block-aligned zero-pad
DO_CASES = [(2048, 25, 64), (1024, 289, 128), (512, 9, 256),
            (1000, 25, 64)]


def _do_tol(dtype, K):
    return dict(rtol=2e-2, atol=K * 2e-3) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=K * 1e-6)


@pytest.mark.parametrize("case", DO_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dict_outer(case, dtype):
    K, P, A = case
    S = jax.random.normal(jax.random.fold_in(KEY, 13), (K, P), dtype)
    W = jax.random.normal(jax.random.fold_in(KEY, 14), (K, A), dtype)
    sw, ww = dict_outer(S, W, use_kernel=True)
    swr, wwr = dict_outer_ref(S, W)
    tol = _do_tol(dtype, K)
    np.testing.assert_allclose(np.asarray(sw), np.asarray(swr), **tol)
    np.testing.assert_allclose(np.asarray(ww), np.asarray(wwr), **tol)


DOP_CASES = [(2048, 289, 81, 128), (1000, 289, 81, 128),
             (512, 25, 9, 256), (130, 25, 9, 128)]


@pytest.mark.parametrize("case", DOP_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dict_outer_pair(case, dtype):
    """The coupled-pair fusion: one grid pass over K produces all four
    outer products, including non-block-aligned sample counts."""
    K, P, M, A = case
    Sh = jax.random.normal(jax.random.fold_in(KEY, 15), (K, P), dtype)
    Sl = jax.random.normal(jax.random.fold_in(KEY, 16), (K, M), dtype)
    Wh = jax.random.normal(jax.random.fold_in(KEY, 17), (K, A), dtype)
    Wl = jax.random.normal(jax.random.fold_in(KEY, 18), (K, A), dtype)
    out = dict_outer_pair(Sh, Sl, Wh, Wl, use_kernel=True)
    ref = dict_outer_pair_ref(Sh, Sl, Wh, Wl)
    tol = _do_tol(dtype, K)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), **tol)


# ---------------------------------------------------------- admm elwise
from repro.kernels.admm_elwise.ops import admm_elwise
from repro.kernels.admm_elwise.ref import admm_elwise_ref

AE_KW = dict(c1=0.4, c2=0.4, c3=0.8, t1=0.025, t2=0.025)
AE_CASES = [(2048, 128), (1000, 256), (130, 128), (512, 512)]


@pytest.mark.parametrize("case", AE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_admm_elwise(case, dtype):
    """Fused soft-threshold + dual updates over the stacked (K, 5, A)
    multiplier state, kernel vs oracle, non-block-aligned K included."""
    K, A = case
    Wh = jax.random.normal(jax.random.fold_in(KEY, 19), (K, A), dtype)
    Wl = jax.random.normal(jax.random.fold_in(KEY, 20), (K, A), dtype)
    YZ = jax.random.normal(jax.random.fold_in(KEY, 21), (K, 5, A), dtype)
    out = admm_elwise(Wh, Wl, YZ, use_kernel=True, **AE_KW)
    ref = admm_elwise_ref(Wh, Wl, YZ, **AE_KW)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_admm_elwise_matches_unfused_formulation():
    """The kernel's clip/fold algebra equals the textbook step 8:
    soft-threshold P/Q then three dual ascent updates and the Z
    right-hand-side combinations."""
    K, A = 257, 64
    c1, c2, c3, t1, t2 = (AE_KW[k] for k in ("c1", "c2", "c3", "t1",
                                             "t2"))
    Wh = jax.random.normal(jax.random.fold_in(KEY, 22), (K, A))
    Wl = jax.random.normal(jax.random.fold_in(KEY, 23), (K, A))
    YZ = jax.random.normal(jax.random.fold_in(KEY, 24), (K, 5, A))
    y1, y2, y3 = YZ[:, 0], YZ[:, 1], YZ[:, 2]
    soft = lambda x, t: jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)
    P = soft(Wh - y1 / c1, t1)
    Q = soft(Wl - y2 / c2, t2)
    Y1 = y1 + c1 * (P - Wh)
    Y2 = y2 + c2 * (Q - Wl)
    Y3 = y3 + c3 * (Wh - Wl)
    Z1 = c1 * P + Y1 - Y3 + c3 * Wl
    Z2 = c2 * Q + Y2 + Y3
    expect = jnp.stack([Y1, Y2, Y3, Z1, Z2], axis=1)
    got = admm_elwise_ref(Wh, Wl, YZ, **AE_KW)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------- condat elwise
from repro.kernels.condat_elwise.ops import condat_dual, condat_primal
from repro.kernels.condat_elwise.ref import (condat_dual_ref,
                                             condat_primal_ref)

# (100, ...) / (130, ...) exercise the ragged last block; 49 rows of
# 41x41 are one past the derived 48-row block, so the last block holds
# a single real row
CP_CASES = [(100, 41), (130, 21), (16, 41), (256, 33), (49, 41)]


@pytest.mark.parametrize("case", CP_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_condat_primal(case, dtype):
    """Fused gradient step + positivity prox (+ over-relaxation for the
    low-rank path), kernel vs oracle, non-block-aligned stacks."""
    N, S = case
    X = jax.random.normal(jax.random.fold_in(KEY, 30), (N, S, S), dtype)
    Ua = jax.random.normal(jax.random.fold_in(KEY, 31), (N, S, S), dtype)
    g = jax.random.normal(jax.random.fold_in(KEY, 32), (N, S, S), dtype)
    out = condat_primal(X, Ua, g, 0.31, use_kernel=True, interpret=True)
    ref = condat_primal_ref(X, Ua, g, 0.31)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
    xn, xb = condat_primal(X, Ua, g, 0.31, with_xbar=True,
                           use_kernel=True, interpret=True)
    rn, rb = condat_primal_ref(X, Ua, g, 0.31, with_xbar=True)
    np.testing.assert_allclose(np.asarray(xn, np.float32),
                               np.asarray(rn, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(xb, np.float32),
                               np.asarray(rb, np.float32), **_tol(dtype))


@pytest.mark.parametrize("case", [(3, 100, 41), (4, 37, 21), (2, 130, 33),
                                  (1, 49, 41)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_condat_dual(case, dtype):
    """Fused over-relaxation + dual clamp over the (J, n, S, S) stack
    with the (J, n, 1, 1) weight column broadcast, kernel vs oracle on
    non-block-aligned flattened sizes."""
    J, N, S = case
    U = jax.random.normal(jax.random.fold_in(KEY, 33), (J, N, S, S), dtype)
    Cn = jax.random.normal(jax.random.fold_in(KEY, 34), (J, N, S, S), dtype)
    Co = jax.random.normal(jax.random.fold_in(KEY, 35), (J, N, S, S), dtype)
    W = jax.random.uniform(jax.random.fold_in(KEY, 36), (J, N, 1, 1),
                           jnp.float32).astype(dtype)
    out = condat_dual(U, Cn, Co, W, 0.47, use_kernel=True, interpret=True)
    ref = condat_dual_ref(U, Cn, Co, W, 0.47)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_condat_kernels_leave_caller_arrays_unchanged():
    """Each pass writes its first operand's buffer (X, U); an array the
    caller still holds must come back as it went in."""
    J, N, S = 2, 100, 41
    X, Ua, g = (jax.random.normal(jax.random.fold_in(KEY, 45 + i),
                                  (N, S, S)) for i in range(3))
    U, Cn, Co = (jax.random.normal(jax.random.fold_in(KEY, 48 + i),
                                   (J, N, S, S)) for i in range(3))
    W = jax.random.uniform(jax.random.fold_in(KEY, 51), (J, N, 1, 1))
    held = [np.array(a) for a in (X, Ua, g, U, Cn, Co, W)]
    xn = condat_primal(X, Ua, g, 0.31, use_kernel=True, interpret=True)
    xn2, _ = condat_primal(X, Ua, g, 0.31, with_xbar=True,
                           use_kernel=True, interpret=True)
    un = condat_dual(U, Cn, Co, W, 0.47, use_kernel=True, interpret=True)
    for before, after in zip(held, (X, Ua, g, U, Cn, Co, W)):
        np.testing.assert_array_equal(np.asarray(after), before)
    # the updates did happen
    assert not np.array_equal(np.asarray(xn), held[0])
    np.testing.assert_array_equal(np.asarray(xn2), np.asarray(xn))
    assert not np.array_equal(np.asarray(un), held[3])


def test_condat_dual_matches_unfused_formulation():
    """The fused pass equals the textbook dual step: V = U + sig
    Phi(X_bar) with Phi(X_bar) = 2 C_new - C_old, then clamp to
    [-W, W]."""
    J, N, S = 3, 64, 41
    sig = 0.8
    U = jax.random.normal(jax.random.fold_in(KEY, 37), (J, N, S, S))
    Cn = jax.random.normal(jax.random.fold_in(KEY, 38), (J, N, S, S))
    Co = jax.random.normal(jax.random.fold_in(KEY, 39), (J, N, S, S))
    W = jax.random.uniform(jax.random.fold_in(KEY, 40), (J, N, 1, 1))
    got = condat_dual(U, Cn, Co, W, sig)
    expect = jnp.clip(U + sig * (2 * Cn - Co), -W, W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)
