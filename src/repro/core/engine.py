"""Distributed iteration engine: one shard_map per learning iteration.

The paper's execution model (Fig. 1b) is: driver fires an action -> the
task manager ships one stage per partition to the workers -> partial
results reduce back to the driver.  Here a learning iteration is ONE
``shard_map``-wrapped pure function over the bundle:

    step(local_blocks, replicated) -> (new_local_blocks, reduced_scalars)

Everything record-local runs without communication; anything cross-
partition (cost sums, Gram matrices, dictionary outer products) is a
``psum`` inside the step — the all-reduce that replaces Spark's driver
round-trip.  The returned step is jit-compiled once and reused across
iterations (Spark's lazy DAG -> XLA's staged graph).

:func:`make_scan_step` goes one level further (DESIGN.md §12): K
iterations are fused into ONE dispatch via ``jax.lax.scan`` inside the
shard_map, carrying ``(data, replicated)`` on-device and accumulating a
``(K,)`` cost buffer — the host only syncs once per chunk, removing the
per-iteration driver round-trip that the paper identifies as Spark's
dominant overhead.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.bundle import Bundle


def make_step(fn: Callable, bundle: Bundle, *, donate: bool = True,
              static_replicated: bool = False):
    """Compile ``fn(data_local, replicated, axes) -> (data_local', out)``
    into a jitted distributed step over the bundle's mesh.

    ``axes`` is the tuple of mesh axis names to psum over (empty when the
    bundle is unpartitioned, e.g. the sequential reference).  ``out`` must
    be replicated-safe (i.e. already psum-reduced by ``fn``).
    """
    axes = bundle.axes

    if bundle.mesh is None:
        def local_step(data, rep):
            return fn(data, rep, ())
        return jax.jit(local_step, donate_argnums=(0,) if donate else ())

    data_spec = jax.tree.map(lambda _: bundle.record_spec(), bundle.data)
    rep_spec = jax.tree.map(lambda _: P(), bundle.replicated)
    out_data_shape, out_shape = jax.eval_shape(
        lambda d, r: fn(d, r, ()),
        _local_shapes(bundle), bundle.replicated)
    out_data_spec = jax.tree.map(lambda _: bundle.record_spec(),
                                 out_data_shape)
    out_rep_spec = jax.tree.map(lambda _: P(), out_shape)

    def local(data, rep):
        return fn(data, rep, axes)

    mapped = shard_map(
        local, mesh=bundle.mesh,
        in_specs=(data_spec, rep_spec),
        out_specs=(out_data_spec, out_rep_spec),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def _local_shapes(bundle: Bundle):
    n = max(bundle.n_partitions, 1)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((x.shape[0] // n,) + x.shape[1:],
                                       x.dtype), bundle.data)


def _scalar_trace(out):
    """The per-iteration trace kept by the fused scan: scalar leaves only
    (costs/metrics).  Matrix-valued outputs (e.g. SCDL's dictionaries)
    feed the replicated carry instead of being stacked K times."""
    if isinstance(out, dict):
        kept = {k: v for k, v in out.items() if jnp.ndim(v) == 0}
        return kept if kept else out
    return out


def out_struct(fn: Callable, bundle: Bundle):
    """Shape/dtype structure of ``fn``'s reduced output (the ``out`` of
    ``fn(data_local, replicated, axes) -> (data', out)``)."""
    _, shape = jax.eval_shape(lambda d, r: fn(d, r, ()),
                              _local_shapes(bundle), bundle.replicated)
    return shape


def _seed_like(shapes):
    """Seed a shape tree with the "not yet evaluated" convention: float
    leaves get +inf (a resume landing off the cost grid then logs inf,
    which can never fake convergence), other dtypes zeros."""
    def seed(s):
        if jnp.issubdtype(s.dtype, jnp.floating):
            return jnp.full(s.shape, jnp.inf, s.dtype)
        return jnp.zeros(s.shape, s.dtype)
    return jax.tree.map(seed, shapes)


def init_out_like(fn: Callable, bundle: Bundle):
    """Initial carried output for a ``cost_every``-skipping scan step."""
    return _seed_like(out_struct(fn, bundle))


def init_cost_like(fn_cost: Callable, bundle: Bundle):
    """Initial carried objective for the per-chunk cost mode:
    ``fn_cost(data_local, replicated, axes) -> out`` (no data return)."""
    return _seed_like(jax.eval_shape(lambda d, r: fn_cost(d, r, ()),
                                     _local_shapes(bundle),
                                     bundle.replicated))


def make_scan_step(fn: Callable, bundle: Bundle, *, chunk: int = 8,
                   donate: bool = True,
                   update_replicated: Optional[Callable] = None,
                   fn_light: Optional[Callable] = None,
                   cost_every: int = 1,
                   light_updates_replicated: bool = False):
    """Fuse ``chunk`` iterations of ``fn`` into one on-device dispatch.

    Compiles ``step(data, replicated, start) -> (data', replicated',
    trace)`` where ``trace`` stacks the scalar leaves of ``fn``'s reduced
    output into ``(chunk,)`` buffers.  ``start`` is the global iteration
    index of the chunk's first iteration (drives ``cost_every`` phasing).

    - ``update_replicated(replicated, out) -> replicated'`` folds each
      iteration's reduced output back into the broadcast state *inside*
      the scan carry — the paper's per-iteration driver broadcast (SCDL
      step 7) without leaving the device.  The hook may post-process the
      reduced output (e.g. factor the SCDL Gram matrices into broadcast
      solve operators, DESIGN.md §13) — its result replaces the whole
      replicated carry.
    - ``fn_light(data, replicated, axes) -> data'`` is the cost-free
      variant of ``fn``; when given and ``cost_every > 1``, iterations
      off the cost grid run it and carry the last computed output
      forward instead of re-evaluating the objective.  The step then
      takes a fourth argument and returns it updated — ``step(data,
      replicated, start, last_out) -> (data', replicated', last_out',
      trace)`` — so the carried output survives chunk boundaries (seed
      it with :func:`init_out_like`; iteration 0 always evaluates).
    - ``light_updates_replicated=True`` declares that the broadcast
      state must advance on *every* iteration, not just evaluated ones
      (SCDL's dictionary update is part of the iterate, not of the
      objective).  ``fn_light`` then returns ``(data', out_partial)``
      where ``out_partial`` is a dict holding the subset of ``fn``'s
      output keys that feed ``update_replicated``; off-grid iterations
      merge it over the carried output (fresh broadcast inputs, stale
      scalars) and apply the hook unconditionally.
    """
    axes = bundle.axes
    use_light = fn_light is not None and cost_every > 1

    def body(carry, i):
        d, r, last = carry
        if use_light and light_updates_replicated:
            def on_grid(dd, rr, lo):
                return fn(dd, rr, axes)

            def off_grid(dd, rr, lo):
                d2, aux = fn_light(dd, rr, axes)
                return d2, {**lo, **aux}

            d2, out = jax.lax.cond(i % cost_every == 0,
                                   on_grid, off_grid, d, r, last)
            r2 = update_replicated(r, out) if update_replicated else r
        elif use_light:
            d2, out = jax.lax.cond(
                i % cost_every == 0,
                lambda dd, rr, lo: fn(dd, rr, axes),
                lambda dd, rr, lo: (fn_light(dd, rr, axes), lo),
                d, r, last)
            # apply the broadcast update only on evaluated iterations —
            # ``out`` is the stale carry otherwise, and the per-step
            # driver path skips the update there too
            r2 = (jax.lax.cond(i % cost_every == 0,
                               lambda: update_replicated(r, out),
                               lambda: r)
                  if update_replicated else r)
        else:
            d2, out = fn(d, r, axes)
            r2 = update_replicated(r, out) if update_replicated else r
        return (d2, r2, out), _scalar_trace(out)

    if use_light:
        def chunk_fn(data, rep, start, last):
            (d, r, last2), trace = jax.lax.scan(
                body, (data, rep, last), start + jnp.arange(chunk))
            return d, r, last2, trace
    else:
        def chunk_fn(data, rep, start):
            init = init_out_like(fn, bundle)      # never observed
            (d, r, _), trace = jax.lax.scan(
                body, (data, rep, init), start + jnp.arange(chunk))
            return d, r, trace

    # donate the carried-output buffer alongside the data blocks: the
    # step returns an identically-shaped tree, so XLA aliases it
    # in-place instead of allocating per dispatch
    donated = ((0, 3) if use_light else (0,)) if donate else ()
    if bundle.mesh is None:
        return jax.jit(chunk_fn, donate_argnums=donated)

    out_shape = out_struct(fn, bundle)
    data_spec = jax.tree.map(lambda _: bundle.record_spec(), bundle.data)
    rep_spec = jax.tree.map(lambda _: P(), bundle.replicated)
    out_spec = jax.tree.map(lambda _: P(), out_shape)
    trace_spec = jax.tree.map(lambda _: P(), _scalar_trace(out_shape))
    if use_light:
        in_specs = (data_spec, rep_spec, P(), out_spec)
        out_specs = (data_spec, rep_spec, out_spec, trace_spec)
    else:
        in_specs = (data_spec, rep_spec, P())
        out_specs = (data_spec, rep_spec, trace_spec)

    mapped = shard_map(
        chunk_fn, mesh=bundle.mesh,
        in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return jax.jit(mapped, donate_argnums=donated)


# --------------------------------------------------------------------
# Batched multi-instance steps (solve_many, DESIGN.md §19)
# --------------------------------------------------------------------
#
# The batched state is ``{"d": data, "r": replicated_batched[, "last":
# carried_out]}`` with every leaf carrying a leading instance axis B;
# the bucket-shared replicated tree (``BatchAxes.shared_in_batch``)
# rides separately and is broadcast.  The per-instance step runs under
# ``vmap`` with ``axes=()`` — instances never psum into each other;
# cross-device sharding splits the *batch* axis instead of the record
# axis, so each device owns whole instances.


def _bcast_mask(active, leaf):
    return jnp.reshape(active, active.shape + (1,) * (leaf.ndim - 1))


def freeze_where(active, new, old):
    """Per-instance freeze: re-select ``old`` wherever the active mask
    is False, so converged (or padded-filler) lanes stay bitwise
    constant while live lanes advance.  Frozen lanes still *compute* —
    masking discards the result — which is the price of keeping one
    fused program; re-compaction (BatchedDriver) reclaims the FLOPs
    once enough lanes retire."""
    return jax.tree.map(
        lambda n, o: jnp.where(_bcast_mask(active, n), n, o), new, old)


def _instance_struct(tree):
    """Shape/dtype structure of one instance (leading batch axis
    dropped)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape[1:]), x.dtype),
        tree)


def _merge_rep(r_i, shared):
    """One instance's full replicated view: its batched slice overlaid
    on the bucket-shared tree.  Non-dict replicated trees cannot split,
    so they are all-batched (shared must be empty/None)."""
    if shared is None:
        return r_i
    if isinstance(shared, dict) and isinstance(r_i, dict):
        return {**shared, **r_i} if shared else r_i
    if not shared:
        return r_i
    raise TypeError(
        "shared_in_batch requires dict-shaped replicated state")


def _split_rep(rep_full, r_i):
    """Project an updated full replicated view back onto the batched
    keys (the shared part is constant by declaration)."""
    if isinstance(r_i, dict):
        return {k: rep_full[k] for k in r_i}
    return rep_full


def _seed_like_batched(shapes, batch: int):
    return jax.tree.map(
        lambda s: (jnp.full((batch,) + tuple(s.shape), jnp.inf, s.dtype)
                   if jnp.issubdtype(s.dtype, jnp.floating)
                   else jnp.zeros((batch,) + tuple(s.shape), s.dtype)),
        shapes)


def _batch_size(state) -> int:
    return jax.tree.leaves(state["d"])[0].shape[0]


def _instance_out_struct(fn: Callable, state, shared):
    d_i = _instance_struct(state["d"])
    rep_i = _merge_rep(_instance_struct(state["r"]), shared)
    return jax.eval_shape(lambda d, r: fn(d, r, ()), d_i, rep_i)


def init_batched_out_like(fn: Callable, state, shared):
    """(B,)-stacked +inf seed of ``fn``'s per-instance reduced output
    (the carried slot for cost-skipping batched scans)."""
    _, out = _instance_out_struct(fn, state, shared)
    return _seed_like_batched(out, _batch_size(state))


def init_batched_cost_like(fn_cost: Callable, state, shared):
    """(B,)-stacked +inf seed of the per-instance objective (per-chunk
    cost mode)."""
    out = _instance_out_struct(fn_cost, state, shared)
    return _seed_like_batched(out, _batch_size(state))


def _batched_specs(bundle: Bundle, state):
    """shard_map specs for the batched step: state leaves split on the
    batch axis, shared replicated + the start index stay replicated,
    traces are (chunk, B) with B split."""
    bspec = bundle.record_spec()
    state_spec = jax.tree.map(lambda _: bspec, state)
    shared_spec = jax.tree.map(lambda _: P(), bundle.replicated)
    trace_spec = P(None, bundle.axes) if bundle.axes else P()
    return bspec, state_spec, shared_spec, trace_spec


def make_batched_scan_step(fn: Callable, bundle: Bundle, state, *,
                           chunk: int = 8, donate: bool = True,
                           update_replicated: Optional[Callable] = None,
                           fn_light: Optional[Callable] = None,
                           cost_every: int = 1,
                           light_updates_replicated: bool = False):
    """Fuse ``chunk`` iterations across a whole bucket of instances
    into one dispatch: the batched analogue of :func:`make_scan_step`.

    Compiles ``step(state, shared, active, start) -> (state', trace)``
    where ``state`` is the batched carry described above, ``shared`` is
    the bucket-shared replicated tree, ``active`` is the per-instance
    convergence mask (frozen lanes re-select their previous carry via
    :func:`freeze_where` every iteration) and ``trace`` stacks the
    per-instance scalar outputs into ``(chunk, B)`` buffers.  The
    ``cost_every``/``fn_light``/``update_replicated`` semantics mirror
    the single-instance factory, applied per instance under ``vmap``
    (the cost-grid ``lax.cond`` predicate is batch-invariant, so it
    stays a real branch).
    """
    use_light = fn_light is not None and cost_every > 1
    has_last = "last" in state

    def iter_i(d_i, r_i, shared, last_i, i):
        rep = _merge_rep(r_i, shared)
        if use_light and light_updates_replicated:
            def on_grid(dd, lo):
                return fn(dd, rep, ())

            def off_grid(dd, lo):
                d2, aux = fn_light(dd, rep, ())
                return d2, {**lo, **aux}

            d2, out = jax.lax.cond(i % cost_every == 0,
                                   on_grid, off_grid, d_i, last_i)
            r2 = (_split_rep(update_replicated(rep, out), r_i)
                  if update_replicated else r_i)
        elif use_light:
            d2, out = jax.lax.cond(
                i % cost_every == 0,
                lambda dd, lo: fn(dd, rep, ()),
                lambda dd, lo: (fn_light(dd, rep, ()), lo),
                d_i, last_i)
            r2 = (jax.lax.cond(
                i % cost_every == 0,
                lambda: _split_rep(update_replicated(rep, out), r_i),
                lambda: r_i)
                if update_replicated else r_i)
        else:
            d2, out = fn(d_i, rep, ())
            r2 = (_split_rep(update_replicated(rep, out), r_i)
                  if update_replicated else r_i)
        return d2, r2, out, _scalar_trace(out)

    biter = jax.vmap(iter_i,
                     in_axes=(0, 0, None, 0 if has_last else None, None))

    def chunk_fn(state, shared, active, start):
        def body(st, i):
            last = st["last"] if has_last else None
            d2, r2, out, tr = biter(st["d"], st["r"], shared, last, i)
            new = {"d": d2, "r": r2}
            if has_last:
                new["last"] = out
            return freeze_where(active, new, st), tr

        st, trace = jax.lax.scan(body, state, start + jnp.arange(chunk))
        return st, trace

    donated = (0,) if donate else ()
    if bundle.mesh is None:
        return jax.jit(chunk_fn, donate_argnums=donated)

    bspec, state_spec, shared_spec, trace_spec = _batched_specs(
        bundle, state)
    _, out = _instance_out_struct(fn, state, bundle.replicated)
    traces = jax.tree.map(lambda _: trace_spec, _scalar_trace(out))
    mapped = shard_map(
        chunk_fn, mesh=bundle.mesh,
        in_specs=(state_spec, shared_spec, bspec, P()),
        out_specs=(state_spec, traces), check_vma=False)
    return jax.jit(mapped, donate_argnums=donated)


def make_batched_chunk_cost_step(fn_light: Callable, fn_cost: Callable,
                                 bundle: Bundle, state, *,
                                 chunk: int = 8, donate: bool = True,
                                 update_replicated: Optional[Callable]
                                 = None):
    """Batched analogue of :func:`make_chunk_cost_step`: the scan body
    runs only the vmapped cost-free step; the per-instance objective is
    evaluated once per dispatch on the chunk's final state and carried
    in ``state["last"]``.  Frozen lanes keep their previous objective —
    the trace a converged instance reports never moves again.

    Same compiled signature as :func:`make_batched_scan_step`:
    ``step(state, shared, active, start) -> (state', trace)``.
    """

    def light_i(d_i, r_i, shared):
        rep = _merge_rep(r_i, shared)
        if update_replicated is None:
            return fn_light(d_i, rep, ()), r_i
        d2, aux = fn_light(d_i, rep, ())
        return d2, _split_rep(update_replicated(rep, aux), r_i)

    def cost_i(d_i, r_i, shared):
        return fn_cost(d_i, _merge_rep(r_i, shared), ())

    blight = jax.vmap(light_i, in_axes=(0, 0, None))
    bcost = jax.vmap(cost_i, in_axes=(0, 0, None))

    def chunk_fn(state, shared, active, start):
        def body(st, _):
            d2, r2 = blight(st["d"], st["r"], shared)
            return freeze_where(active, {"d": d2, "r": r2}, st), None

        core, _ = jax.lax.scan(
            body, {"d": state["d"], "r": state["r"]}, None, length=chunk)
        fresh = bcost(core["d"], core["r"], shared)
        fresh = freeze_where(active, fresh, state["last"])
        trace = jax.tree.map(
            lambda s, f: jnp.concatenate(
                [jnp.broadcast_to(s, (chunk - 1,) + jnp.shape(s)),
                 jnp.asarray(f)[None]]), state["last"], fresh)
        return dict(core, last=fresh), trace

    donated = (0,) if donate else ()
    if bundle.mesh is None:
        return jax.jit(chunk_fn, donate_argnums=donated)

    bspec, state_spec, shared_spec, trace_spec = _batched_specs(
        bundle, state)
    cost_shape = _instance_out_struct(fn_cost, state, bundle.replicated)
    traces = jax.tree.map(lambda _: trace_spec, cost_shape)
    mapped = shard_map(
        chunk_fn, mesh=bundle.mesh,
        in_specs=(state_spec, shared_spec, bspec, P()),
        out_specs=(state_spec, traces), check_vma=False)
    return jax.jit(mapped, donate_argnums=donated)


def make_chunk_cost_step(fn_light: Callable, fn_cost: Callable,
                         bundle: Bundle, *, chunk: int = 8,
                         donate: bool = True,
                         update_replicated: Optional[Callable] = None):
    """Chunk-granular objective: the fastest execution mode (DESIGN.md
    §13).  The scan body runs ONLY the cost-free step — no ``lax.cond``,
    no stale-output carry threading through the scan — and the objective
    is evaluated once per dispatch, on the chunk's final state.  That is
    exactly the granularity the host observes anyway: the driver syncs
    and checks convergence once per chunk.

    - ``fn_light(data, replicated, axes) -> (data', out_partial)`` with
      ``out_partial`` feeding ``update_replicated`` every iteration (the
      ``light_updates_replicated`` contract).  When ``update_replicated``
      is ``None`` the broadcast state is constant across the scan and
      ``fn_light`` may return bare ``data'`` instead (the plain
      cost-free-step contract, e.g. deconvolution) — the Problem-API
      wiring rules in DESIGN.md §14 rely on this.
    - ``fn_cost(data, replicated, axes) -> out`` evaluates the objective
      scalars from the *post-iteration* state (the broadcast carry holds
      the iteration's reduced results).

    Compiles ``step(data, replicated, start, last) -> (data',
    replicated', out, trace)`` where ``trace`` holds ``last`` (the
    previous chunk's objective, +inf before the first evaluation —
    :func:`init_cost_like`) for the first ``chunk - 1`` slots and the
    fresh objective in the last slot.
    """
    axes = bundle.axes

    def body(carry, _):
        d, r = carry
        if update_replicated is None:
            d2 = fn_light(d, r, axes)
            r2 = r
        else:
            d2, aux = fn_light(d, r, axes)
            r2 = update_replicated(r, aux)
        return (d2, r2), None

    def chunk_fn(data, rep, start, last):
        (d, r), _ = jax.lax.scan(body, (data, rep), None, length=chunk)
        fresh = fn_cost(d, r, axes)
        trace = jax.tree.map(
            lambda s, f: jnp.concatenate(
                [jnp.broadcast_to(s, (chunk - 1,) + jnp.shape(s)),
                 jnp.asarray(f)[None]]), last, fresh)
        return d, r, fresh, trace

    donated = (0, 3) if donate else ()
    if bundle.mesh is None:
        return jax.jit(chunk_fn, donate_argnums=donated)

    cost_shape = jax.eval_shape(lambda d, r: fn_cost(d, r, ()),
                                _local_shapes(bundle), bundle.replicated)
    data_spec = jax.tree.map(lambda _: bundle.record_spec(), bundle.data)
    rep_spec = jax.tree.map(lambda _: P(), bundle.replicated)
    cost_spec = jax.tree.map(lambda _: P(), cost_shape)
    mapped = shard_map(
        chunk_fn, mesh=bundle.mesh,
        in_specs=(data_spec, rep_spec, P(), cost_spec),
        out_specs=(data_spec, rep_spec, cost_spec, cost_spec),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=donated)
