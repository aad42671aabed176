"""Fused SW^T / WW^T outer products — Pallas TPU kernel.

The SCDL dictionary update (paper Eq. 6-7) reduces S^T W (P x A) and
W^T W (A x A) over the sample axis K ~ 40k every iteration.  Doing the
two einsums separately streams W from HBM twice; the fused kernel reads
each (block_k x A) code tile once and feeds BOTH accumulators while the
tile is in VMEM — the arithmetic-intensity fix for the use case's
dominant reduction (and the local half of the paper's step-9 map-reduce;
the psum over shards happens outside).

:func:`dict_outer_pair_fwd` extends this to Algorithm 2's coupled
high/low-resolution pairs: one grid pass over K accumulates all four
reductions (Sh^T Wh, Sl^T Wl, Wh^T Wh, Wl^T Wl), so each code tile is
read from HBM exactly once per iteration instead of twice per pair.

Grid: (K / block_k,) sequential accumulation into VMEM-resident (P, A)
and (A, A) fp32 accumulators (dimension_semantics: arbitrary — the
revisit order is the accumulation).  VMEM bound: the accumulators must
fit on-chip — (P+A) x A x 4 B for the single kernel, (P+M+2A) x A x 4 B
for the pair — which holds through the paper's default A = 512
(~2.3 MB / ~4.3 MB at the GS shape) but NOT at its A = 2056 sweep
point; an A-axis-blocked variant would be needed there.  Sample counts
that don't divide ``block_k`` are zero-padded up to a whole block (zero
rows contribute nothing to either accumulator).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import auto_interpret, pad_leading


def _outer_kernel(s_ref, w_ref, sw_ref, ww_ref):
    ki = pl.program_id(0)
    s = s_ref[...].astype(jnp.float32)                  # (bk, P)
    w = w_ref[...].astype(jnp.float32)                  # (bk, A_blk)

    @pl.when(ki == 0)
    def _init():
        sw_ref[...] = jnp.zeros_like(sw_ref)
        ww_ref[...] = jnp.zeros_like(ww_ref)

    sw_ref[...] += s.T @ w
    ww_ref[...] += w.T @ w


def dict_outer_fwd(S, W, *, block_k: int = 512, interpret=None):
    """S: (K, P); W: (K, A). Returns (S^T W (P, A), W^T W (A, A)) fp32."""
    if interpret is None:
        interpret = auto_interpret()
    K, P = S.shape
    A = W.shape[1]
    block_k = min(block_k, K)
    (S, W), k_full = pad_leading([S, W], block_k)

    return pl.pallas_call(
        _outer_kernel,
        grid=(k_full // block_k,),
        in_specs=[
            pl.BlockSpec((block_k, P), lambda i: (i, 0)),
            pl.BlockSpec((block_k, A), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((P, A), lambda i: (0, 0)),
            pl.BlockSpec((A, A), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, A), jnp.float32),
            jax.ShapeDtypeStruct((A, A), jnp.float32),
        ],
        interpret=interpret,
        name="dict_outer",
    )(S, W)


def _outer_pair_kernel(sh_ref, sl_ref, wh_ref, wl_ref,
                       shwh_ref, slwl_ref, ph_ref, pll_ref):
    ki = pl.program_id(0)
    sh = sh_ref[...].astype(jnp.float32)                # (bk, P)
    sl = sl_ref[...].astype(jnp.float32)                # (bk, M)
    wh = wh_ref[...].astype(jnp.float32)                # (bk, A)
    wl = wl_ref[...].astype(jnp.float32)                # (bk, A)

    @pl.when(ki == 0)
    def _init():
        shwh_ref[...] = jnp.zeros_like(shwh_ref)
        slwl_ref[...] = jnp.zeros_like(slwl_ref)
        ph_ref[...] = jnp.zeros_like(ph_ref)
        pll_ref[...] = jnp.zeros_like(pll_ref)

    # each W tile feeds both of its accumulators while resident in VMEM
    shwh_ref[...] += sh.T @ wh
    ph_ref[...] += wh.T @ wh
    slwl_ref[...] += sl.T @ wl
    pll_ref[...] += wl.T @ wl


def dict_outer_pair_fwd(Sh, Sl, Wh, Wl, *, block_k: int = 512,
                        interpret=None):
    """Coupled-pair fusion: Sh (K, P), Sl (K, M), Wh/Wl (K, A) ->
    (Sh^T Wh (P, A), Sl^T Wl (M, A), Wh^T Wh, Wl^T Wl (A, A)) fp32."""
    if interpret is None:
        interpret = auto_interpret()
    K, P = Sh.shape
    M = Sl.shape[1]
    A = Wh.shape[1]
    block_k = min(block_k, K)
    (Sh, Sl, Wh, Wl), k_full = pad_leading([Sh, Sl, Wh, Wl], block_k)

    return pl.pallas_call(
        _outer_pair_kernel,
        grid=(k_full // block_k,),
        in_specs=[
            pl.BlockSpec((block_k, P), lambda i: (i, 0)),
            pl.BlockSpec((block_k, M), lambda i: (i, 0)),
            pl.BlockSpec((block_k, A), lambda i: (i, 0)),
            pl.BlockSpec((block_k, A), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((P, A), lambda i: (0, 0)),
            pl.BlockSpec((M, A), lambda i: (0, 0)),
            pl.BlockSpec((A, A), lambda i: (0, 0)),
            pl.BlockSpec((A, A), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, A), jnp.float32),
            jax.ShapeDtypeStruct((M, A), jnp.float32),
            jax.ShapeDtypeStruct((A, A), jnp.float32),
            jax.ShapeDtypeStruct((A, A), jnp.float32),
        ],
        interpret=interpret,
        name="dict_outer_pair",
    )(Sh, Sl, Wh, Wl)
