"""GPipe-style pipeline parallelism over a `stage` mesh axis.

For very deep assigned archs (granite-34b: 88 layers) pure TP+DP leaves
the per-chip parameter floor high; an optional pipeline axis splits the
layer stack into S stages of L/S layers, microbatches flowing through a
collective-permute ring.

Implementation: the classic shard_map schedule —
  - params stacked (S, L/S, ...): stage axis sharded over 'stage';
  - loop t in [0, M + S - 1): each stage applies its block to its
    current microbatch (bubble masked), then the activations
    collective-permute to the next stage;
  - loss computed on the last stage, grads flow back through the
    transposed permutes automatically (shard_map AD).

This module is deliberately self-contained (used by tests and the
granite-34b §Perf experiments); the dry-run default path remains DP x TP.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_apply(layer_fn: Callable, stage_params, x_micro, *,
                   stage_axis: str = "stage"):
    """Run microbatches through the pipeline ring (inside shard_map).

    layer_fn(params_block, x) -> x : applies one stage's layer block.
    stage_params: this stage's (L/S, ...) param slice.
    x_micro: (M, mb, ...) all microbatches, resident on every stage
        (stage 0 consumes them in order; later stages ignore the feed and
        use the ring input).
    Returns (M, mb, ...) outputs as produced by the LAST stage, rolled
    back into order.
    """
    n_stage = axis_size(stage_axis)
    stage_id = jax.lax.axis_index(stage_axis)
    M = x_micro.shape[0]
    mb_shape = x_micro.shape[1:]
    total = M + n_stage - 1

    def body(t, carry):
        ring, outputs = carry
        # stage 0 ingests microbatch t (if in range), others take ring
        feed_idx = jnp.clip(t, 0, M - 1)
        feed = x_micro[feed_idx]
        x_in = jnp.where(stage_id == 0, feed, ring)
        y = layer_fn(stage_params, x_in)
        # last stage records its output at slot (t - n_stage + 1)
        out_idx = jnp.clip(t - (n_stage - 1), 0, M - 1)
        is_valid = (t >= n_stage - 1)
        outputs = jax.lax.cond(
            is_valid & (stage_id == n_stage - 1),
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, out_idx, 0),
            lambda o: o, outputs)
        # rotate activations stage i -> i+1
        ring_next = jax.lax.ppermute(
            y, stage_axis,
            [(i, (i + 1) % n_stage) for i in range(n_stage)])
        return ring_next, outputs

    ring0 = jnp.zeros(mb_shape, x_micro.dtype)
    outputs0 = jnp.zeros((M,) + mb_shape, x_micro.dtype)
    _, outputs = jax.lax.fori_loop(0, total, body, (ring0, outputs0))
    # every stage returns `outputs`; only the last stage's is real — make
    # it consistent across the axis for the out_spec
    outputs = jax.lax.psum(
        jnp.where(stage_id == n_stage - 1, outputs, 0.0), stage_axis)
    return outputs


def make_pipelined_forward(layer_fn: Callable, mesh: Mesh, *,
                           n_micro: int, stage_axis: str = "stage",
                           data_axes=("data",)):
    """Build forward(params_staged, x) with pipeline+data parallelism.

    params_staged leaves: (S, L/S, ...) — S sharded over `stage`.
    x: (B, ...) with B % n_micro == 0; microbatch dim scanned through
    the ring.
    """
    def fwd(params_staged, x):
        def local(pstage, xloc):
            M = n_micro
            xm = xloc.reshape((M, xloc.shape[0] // M) + xloc.shape[1:])
            pstage = jax.tree.map(lambda a: a[0], pstage)  # drop stage dim
            ym = pipeline_apply(layer_fn, pstage, xm,
                                stage_axis=stage_axis)
            return ym.reshape(xloc.shape)

        pspec = jax.tree.map(lambda _: P(stage_axis), params_staged)
        xspec = P(data_axes)
        return shard_map(local, mesh=mesh,
                             in_specs=(pspec, xspec),
                             out_specs=xspec, check_vma=False)(
            params_staged, x)

    return fwd
