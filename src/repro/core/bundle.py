"""The paper's core abstraction: the bundled distributed dataset.

Panousopoulou et al. zip k co-partitioned RDDs into one bundled RDD
``D = [D_1 ... D_k]`` (their Fig. 2) so that heterogeneous imaging arrays
that must be processed *jointly* (noisy stamps, per-object PSFs, primal &
dual optimization variables, weighting matrices, multipliers) travel
together through iterative map/reduce learning.

TPU adaptation (DESIGN.md §2): a ``Bundle`` is a pytree of arrays that all
share the same leading-axis partitioning over the mesh's data axes.  The
paper's RDD Bundle / Unbundle components become:

  - ``Bundle.create``  — co-shard k arrays with one PartitionSpec (Bundle);
  - ``bundle_map``     — ``shard_map`` a per-partition function; the user
    function sees plain local arrays, exactly like the worker-side code of
    the paper ("the core principles of the original learning algorithm
    [stay] intact");
  - ``bundle_reduce``  — ``jax.lax.psum`` over the data axes replaces the
    tree-reduce-to-driver: the "driver result" materialises replicated on
    every chip, removing the Spark driver bottleneck.

The number of partitions N maps to the number of data shards (and the
microbatch factor for iterative learners); the persistence model maps to
remat/offload policies in ``core.persistence``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _dp_axes(mesh: Optional[Mesh], axes: Optional[Tuple[str, ...]] = None
             ) -> Tuple[str, ...]:
    if mesh is None:
        return ()
    if axes is None:
        axes = ("pod", "data")
    return tuple(a for a in axes if a in mesh.shape)


@dataclass
class Bundle:
    """k co-partitioned arrays + the mesh/axis they are partitioned over.

    ``data`` is any pytree whose every leaf has the same leading dimension
    N_records; the leading dimension is sharded over ``axes`` of ``mesh``.
    A leaf may opt out of partitioning (broadcast state, e.g. dictionaries)
    by living in ``replicated`` instead — the paper's broadcast variables.
    """
    data: Any
    replicated: Any
    mesh: Optional[Mesh]
    axes: Tuple[str, ...]

    # -------------------------------------------------- construction
    @classmethod
    def create(cls, data: Any, *, mesh: Optional[Mesh] = None,
               replicated: Any = None,
               axes: Optional[Tuple[str, ...]] = None) -> "Bundle":
        axes = _dp_axes(mesh, axes)
        b = cls(data=data, replicated=replicated, mesh=mesh, axes=axes)
        b.validate()
        if mesh is not None:
            dshard = NamedSharding(mesh, b.record_spec())
            rshard = NamedSharding(mesh, P())
            data = jax.tree.map(lambda x: jax.device_put(x, dshard), b.data)
            rep = jax.tree.map(lambda x: jax.device_put(x, rshard),
                               b.replicated)
        else:
            # copy so the iteration engine may donate bundle buffers
            # without invalidating caller-held arrays
            data = jax.tree.map(lambda x: jnp.array(x, copy=True), b.data)
            rep = b.replicated
        return cls(data=data, replicated=rep, mesh=mesh, axes=axes)

    def record_spec(self, extra: int = 0) -> P:
        ax = self.axes if self.axes else None
        return P(ax, *([None] * extra)) if ax else P()

    @property
    def n_records(self) -> int:
        leaves = jax.tree.leaves(self.data)
        return int(leaves[0].shape[0]) if leaves else 0

    @property
    def n_partitions(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.axes:
            n *= self.mesh.shape[a]
        return n

    def validate(self) -> None:
        """The RDD-Bundle invariant: identical leading axis everywhere,
        divisible by the partition count."""
        leaves = jax.tree.leaves(self.data)
        if not leaves:
            return
        n = leaves[0].shape[0]
        for leaf in leaves:
            if leaf.shape[0] != n:
                raise ValueError(
                    f"bundle leaves disagree on leading axis: "
                    f"{leaf.shape[0]} != {n}")
        if self.n_partitions and n % self.n_partitions != 0:
            raise ValueError(
                f"{n} records not divisible into {self.n_partitions} "
                f"partitions")

    # -------------------------------------------------- transformations
    def with_data(self, data: Any, replicated: Any = "keep") -> "Bundle":
        rep = self.replicated if replicated == "keep" else replicated
        return Bundle(data=data, replicated=rep, mesh=self.mesh,
                      axes=self.axes)

    def zip(self, other: "Bundle") -> "Bundle":
        """The paper's RDD.zip: combine two co-partitioned bundles."""
        if other.n_records != self.n_records:
            raise ValueError("zip requires equal record counts")
        return self.with_data((self.data, other.data))


def bundle_map(fn: Callable, bundle: Bundle, *, has_replicated: bool = False
               ) -> Bundle:
    """map: apply ``fn`` partition-wise; no communication.

    ``fn(local_data)`` (or ``fn(local_data, replicated)``) sees the local
    block of every bundled array — the Unbundle component — and returns a
    pytree of updated blocks with unchanged leading axes.
    """
    if bundle.mesh is None:
        out = (fn(bundle.data, bundle.replicated) if has_replicated
               else fn(bundle.data))
        return bundle.with_data(out)

    spec_in = jax.tree.map(lambda _: bundle.record_spec(), bundle.data)
    local_shapes = _local_view(bundle.data, bundle)
    if has_replicated:
        rep_spec = jax.tree.map(lambda _: P(), bundle.replicated)
        local = lambda d, r: fn(d, r)
        out_shape = jax.eval_shape(fn, local_shapes, bundle.replicated)
        spec_out = jax.tree.map(lambda _: bundle.record_spec(), out_shape)
        mapped = shard_map(local, mesh=bundle.mesh,
                               in_specs=(spec_in, rep_spec),
                               out_specs=spec_out, check_vma=False)
        return bundle.with_data(mapped(bundle.data, bundle.replicated))
    out_shape = jax.eval_shape(fn, local_shapes)
    spec_out = jax.tree.map(lambda _: bundle.record_spec(), out_shape)
    mapped = shard_map(fn, mesh=bundle.mesh, in_specs=(spec_in,),
                           out_specs=spec_out, check_vma=False)
    return bundle.with_data(mapped(bundle.data))


def _local_view(data, bundle: Bundle):
    n = max(bundle.n_partitions, 1)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((x.shape[0] // n,) + x.shape[1:],
                                       x.dtype), data)


def bundle_map_reduce(map_fn: Callable, bundle: Bundle, *,
                      has_replicated: bool = False):
    """map+reduce fused: ``map_fn`` returns per-partition partials that are
    psum-reduced over the data axes — the paper's ``map().reduce(add)``
    without the driver round-trip.  Returns a replicated pytree.
    """
    if bundle.mesh is None:
        return (map_fn(bundle.data, bundle.replicated) if has_replicated
                else map_fn(bundle.data))

    axes = bundle.axes

    def local(*args):
        part = map_fn(*args)
        return jax.tree.map(lambda x: jax.lax.psum(x, axes), part)

    spec_in = jax.tree.map(lambda _: bundle.record_spec(), bundle.data)
    local_shapes = _local_view(bundle.data, bundle)
    if has_replicated:
        rep_spec = jax.tree.map(lambda _: P(), bundle.replicated)
        out_shape = jax.eval_shape(map_fn, local_shapes,
                                   bundle.replicated)
        spec_out = jax.tree.map(lambda _: P(), out_shape)
        return shard_map(local, mesh=bundle.mesh,
                             in_specs=(spec_in, rep_spec),
                             out_specs=spec_out, check_vma=False)(
            bundle.data, bundle.replicated)
    out_shape = jax.eval_shape(map_fn, local_shapes)
    spec_out = jax.tree.map(lambda _: P(), out_shape)
    return shard_map(local, mesh=bundle.mesh, in_specs=(spec_in,),
                         out_specs=spec_out, check_vma=False)(bundle.data)


def gather(bundle: Bundle) -> Any:
    """collect(): bring the bundle back to a single host array tree."""
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                        bundle.data)
