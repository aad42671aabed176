"""Distributed-optimization collectives: hierarchical reduction and
int8 error-feedback gradient compression.

At 2+ pods the data-parallel gradient reduction crosses the inter-pod
DCI links, which are far slower than intra-pod ICI.  Two standard tricks,
both expressed as pure shard_map functions so they compose with the
trainer:

  hierarchical_psum : reduce-scatter within the pod, all-reduce the
      scattered shard across pods (1/pod_size of the bytes on the slow
      link), all-gather within the pod — the classic 2-level schedule.

  CompressedReducer : int8 quantisation with error feedback for the
      cross-pod hop.  The quantisation residual is carried to the next
      step (EF-SGD), keeping convergence unbiased to first order; the
      scale factor is per-tensor.  Compression is applied only on the
      `pod` axis where bandwidth is scarce.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.lax import axis_size
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def hierarchical_psum_local(x, *, pod_axis: str = "pod",
                            data_axis: str = "data"):
    """2-level mean-reduction, callable inside shard_map.

    Equivalent to psum over (pod, data) but scheduled as
    reduce_scatter(data) -> psum(pod) -> all_gather(data): the inter-pod
    link carries 1/data_size of the tensor.
    """
    n = x.shape[0]
    data_size = axis_size(data_axis)
    if n % data_size == 0:
        shard = jax.lax.psum_scatter(x, data_axis, scatter_dimension=0,
                                     tiled=True)
        shard = jax.lax.psum(shard, pod_axis)
        return jax.lax.all_gather(shard, data_axis, axis=0, tiled=True)
    # ragged first dim: fall back to flat psum
    return jax.lax.psum(jax.lax.psum(x, data_axis), pod_axis)


def quantize_int8(x) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantisation."""
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_cross_pod_mean(x, error, *, pod_axis: str = "pod"):
    """EF-int8 mean over the pod axis (inside shard_map).

    Returns (mean_estimate, new_error).  The residual (what int8 lost)
    is added back to next step's tensor before quantising — standard
    error feedback.
    """
    pod_size = axis_size(pod_axis)
    corrected = x + error
    q, scale = quantize_int8(corrected)
    decoded = dequantize_int8(q, scale)
    new_error = corrected - decoded
    # int8 payload all-reduce: sum of dequantised views (the wire format
    # would be int8 + one f32 scale per pod; jax models the math)
    summed = jax.lax.psum(decoded, pod_axis)
    return summed / pod_size, new_error


class CompressedReducer:
    """Gradient reducer with persistent error-feedback state.

    Usage in the trainer (per step, inside shard_map over ('pod','data')):
        mean_g, ef = reducer.reduce(g, ef)
    """

    def __init__(self, mesh: Mesh, *, pod_axis: str = "pod",
                 data_axis: str = "data"):
        self.mesh = mesh
        self.pod_axis = pod_axis
        self.data_axis = data_axis

    def init_error(self, grads):
        return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                            grads)

    def reduce_local(self, grads, error):
        """Inside shard_map: intra-pod exact mean, cross-pod EF-int8."""
        def one(g, e):
            g = jax.lax.pmean(g, self.data_axis)
            if self.pod_axis in self.mesh.shape:
                return compressed_cross_pod_mean(g, e,
                                                 pod_axis=self.pod_axis)
            return g, e
        flat_g, td = jax.tree.flatten(grads)
        flat_e = td.flatten_up_to(error)
        out = [one(g, e) for g, e in zip(flat_g, flat_e)]
        return (jax.tree.unflatten(td, [o[0] for o in out]),
                jax.tree.unflatten(td, [o[1] for o in out]))
