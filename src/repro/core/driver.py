"""IterativeDriver: the paper's driver program, generalized.

Runs phase (a) configuration, (b) parallelization (bundle creation), and
(c) iterative task execution with convergence tracking — plus the parts a
production system needs that Spark gave the paper for free or not at all:
checkpoint/restart hooks, straggler watchdog (step-time EMA), and elastic
re-partitioning on restore (``repro.checkpoint``).

Execution modes (DESIGN.md §12):

- ``chunk=1``  — one dispatch + one host sync per iteration (the paper's
  Spark driver loop, and the baseline for ``benchmarks/bench_driver``);
- ``chunk=K>1`` — K iterations fused on-device via
  ``core.engine.make_scan_step``: the host sees one dispatch, one
  ``(K,)`` cost buffer, and one convergence check per chunk.  Broadcast
  state (``update_replicated``) is folded into the scan carry, so
  learners with per-iteration driver broadcasts (SCDL's dictionaries)
  run through this same generic loop.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from dataclasses import dataclass, field, fields, replace
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import checks as _checks
from repro.core import persistence as _persist
from repro.core.bundle import Bundle
from repro.core.engine import (init_batched_cost_like,
                               init_batched_out_like, init_cost_like,
                               init_out_like, make_batched_chunk_cost_step,
                               make_batched_scan_step, make_chunk_cost_step,
                               make_scan_step, make_step)
# dependency-light resilience pieces (chaos injectors are no-ops unless a
# ChaosConfig is activated; the supervisor itself is imported lazily only
# when RunOptions.resilience is set)
from repro.resilience import chaos as _chaos
from repro.resilience.errors import DivergenceError
from repro.resilience.recovery import RecoveryReport, ResilienceConfig


@dataclass(frozen=True)
class RunOptions:
    """Everything the driver needs beyond ``(step_fn, bundle)``.

    One dataclass replaces the former kwarg sprawl of
    ``IterativeDriver.__init__`` (DESIGN.md §14).  Two kinds of fields:

    - *run control* — iteration budget, convergence, chunking,
      observability and checkpoint cadence.  These are what callers of
      :func:`repro.core.problem.solve` override per run.
    - *step wiring* — the cost-free/objective-only step variants and the
      broadcast-update hook.  Hand-wired drivers set these directly;
      ``solve()`` derives them from a :class:`~repro.core.problem.Problem`
      declaration.

    ``cost_every`` accepts an int (evaluate the objective every k-th
    iteration; requires ``step_fn_light``) or the string ``"chunk"``
    (one evaluation per dispatched chunk on its final state; requires
    ``step_fn_cost`` — the fastest observability mode, DESIGN.md §13).
    """
    # run control
    max_iter: int = 300
    tol: float = 1e-4
    chunk: int = 8
    cost_every: Union[int, str] = 1
    cost_window: int = 3
    straggler_factor: float = 3.0
    checkpoint_every: int = 0
    checkpoint_fn: Optional[Callable] = None
    # runtime contract sanitizers (repro.core.checks; also force-enabled
    # by REPRO_CHECKS=1 when going through solve()).  Off by default:
    # the disabled path adds zero dispatches or host transfers.
    checks: bool = False
    # supervised execution (repro.resilience, DESIGN.md §18): retry,
    # divergence rollback, recovery report.  None = unsupervised; the
    # disabled path adds zero dispatches or host transfers.
    resilience: Optional[ResilienceConfig] = None
    # per-chunk observability hook (repro.serve, DESIGN.md §20): called
    # at every chunk-boundary host sync with a progress-event dict —
    # iteration range, evaluated costs, wall time, convergence state
    # (and per-instance entries for batched runs).  The callback runs on
    # the driver's thread at an already-paid sync point, so a cheap
    # callback adds no dispatches; exceptions propagate and abort the
    # run (relays must do their own shielding).
    #
    # Control return (§21): the callback may return a dict to steer the
    # run — {"stop": True} halts at this chunk boundary (RunLog records
    # cancelled_at), and for batched drivers {"cancel_instances": [j..]}
    # freezes the named original-index lanes exactly like converged
    # ones (re-compacted on the next pass, siblings unperturbed).  A
    # None/falsy return (the common case) changes nothing.
    progress_fn: Optional[Callable] = None
    # step wiring
    step_fn_light: Optional[Callable] = None
    step_fn_cost: Optional[Callable] = None
    update_replicated: Optional[Callable] = None
    light_updates_replicated: bool = False

    def __post_init__(self):
        if isinstance(self.cost_every, str):
            if self.cost_every != "chunk":
                raise ValueError(
                    f'cost_every must be a positive int or the string '
                    f'"chunk", got {self.cost_every!r}')
        elif int(self.cost_every) <= 0:
            raise ValueError(
                f'cost_every must be a positive int or the string '
                f'"chunk", got {self.cost_every!r} (0 or negative would '
                f'never evaluate the objective)')
        if int(self.chunk) <= 0:
            raise ValueError(
                f"chunk must be a positive int (iterations fused per "
                f"dispatch), got {self.chunk!r}")

    def merged_with(self, **overrides) -> "RunOptions":
        """A copy with the non-None entries of ``overrides`` applied
        (unknown keys raise, matching dataclasses.replace)."""
        return replace(self, **{k: v for k, v in overrides.items()
                                if v is not None})


_RUN_OPTION_NAMES = tuple(f.name for f in fields(RunOptions))


def percentiles(values, qs=(50, 90, 99)) -> Dict[str, float]:
    """``{"p50": ..., "p90": ..., "p99": ...}`` summary of a sample.

    The one timing-summary helper shared by :meth:`RunLog.percentiles`
    (per-iteration wall times a run already records) and the serving
    metrics registry (request latencies, ``repro.serve.metrics``) — so
    a ``Solution`` and a server report the same statistic the same way.
    Empty input returns an empty dict rather than NaNs.
    """
    vals = np.asarray(list(values), dtype=np.float64)
    if vals.size == 0:
        return {}
    return {f"p{int(q) if float(q).is_integer() else q}":
            float(np.percentile(vals, q)) for q in qs}


def _chunk_span(part: str, start: int, **args):
    """Host span of one chunk in the profiler's trace: ``repro.chunk``
    (``part=""``, from dispatch to the end of the chunk's host work) or
    ``repro.chunk.<part>`` inside it.  Every span of a chunk carries
    ``chunk=<start iteration>``; with no profiler running a span costs
    about a microsecond."""
    name = f"repro.chunk.{part}" if part else "repro.chunk"
    return jax.profiler.TraceAnnotation(name, chunk=int(start), **args)


def _costs_to_host(trace, start: int) -> np.ndarray:
    """The chunk's cost buffer on the host: the wait for the device
    (``repro.chunk.wait``), then the copy (``repro.chunk.fetch``)."""
    costs = trace["cost"] if isinstance(trace, dict) else trace
    with _chunk_span("wait", start):
        costs = jax.block_until_ready(costs)
    with _chunk_span("fetch", start):
        return np.asarray(jax.device_get(costs))


@dataclass
class RunLog:
    costs: List[float] = field(default_factory=list)
    times: List[float] = field(default_factory=list)
    straggler_steps: List[int] = field(default_factory=list)
    converged_at: Optional[int] = None
    # iterations this instance actually advanced — for single solves
    # that equals len(costs); for solve_many lanes frozen by the active
    # mask it stops growing at convergence while the bucket runs on
    iters_run: Optional[int] = None
    # set when the run was halted by a progress_fn control return
    # (serve-layer cancel / deadline expiry, §21) rather than by
    # convergence — the last iteration the instance advanced through
    cancelled_at: Optional[int] = None

    @property
    def total_seconds(self) -> float:
        return float(np.sum(self.times)) if self.times else 0.0

    def percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        """Percentile summary (seconds) of the per-iteration wall times
        this log already records — the per-chunk dt is amortized over
        the chunk's iterations, so p50/p99 read as time-per-iteration.
        Empty log -> empty dict."""
        return percentiles(self.times, qs)


class IterativeDriver:
    """Drive step(state) -> (state, cost) to convergence.

    ``step_fn(data_local, replicated, axes) -> (data_local', out)`` is
    compiled once (per chunk length) and applied until the relative cost
    change drops below ``tol`` (the paper's epsilon) or ``max_iter`` is
    hit.  ``out`` is either a scalar cost or a dict with a ``"cost"``
    entry plus optional replicated state consumed by
    ``options.update_replicated``.

    All remaining configuration lives in one :class:`RunOptions`.  The
    former individual kwargs (``max_iter=``, ``step_fn_light=``, ...) are
    still accepted but deprecated: they are mapped onto ``options`` with
    a ``DeprecationWarning``.
    """

    def __init__(self, step_fn: Callable, bundle: Bundle, *,
                 options: Optional[RunOptions] = None, **legacy):
        if legacy:
            unknown = set(legacy) - set(_RUN_OPTION_NAMES)
            if unknown:
                raise TypeError(
                    f"IterativeDriver got unexpected kwargs {sorted(unknown)}; "
                    f"valid RunOptions fields: {list(_RUN_OPTION_NAMES)}")
            warnings.warn(
                "passing IterativeDriver configuration as individual "
                f"kwargs ({sorted(legacy)}) is deprecated; pass "
                "options=RunOptions(...) instead (DESIGN.md §14)",
                DeprecationWarning, stacklevel=2)
            options = replace(options or RunOptions(), **legacy)
        self.options = options = options or RunOptions()
        self.bundle = bundle
        self.step_fn = step_fn
        self.step_fn_light = options.step_fn_light
        self.step_fn_cost = options.step_fn_cost
        self.update_replicated = options.update_replicated
        self.light_updates_replicated = options.light_updates_replicated
        self.max_iter = options.max_iter
        self.tol = options.tol
        self.cost_window = options.cost_window
        self.straggler_factor = options.straggler_factor
        self.checkpoint_every = options.checkpoint_every
        self.checkpoint_fn = options.checkpoint_fn
        self.checks = options.checks
        self.progress_fn = options.progress_fn
        # a chunk longer than the whole run would compile a scan program
        # that only ever executes its shorter tail — clamp so the one
        # program that runs is the one that was asked for
        self.chunk = max(min(int(options.chunk),
                             max(int(options.max_iter), 1)), 1)
        # same clamp for the checkpoint cadence (0 stays "disabled"): a
        # cadence longer than the run would otherwise never fire, and the
        # final state is exactly what a resume needs
        if self.checkpoint_every:
            self.checkpoint_every = min(int(self.checkpoint_every),
                                        max(int(options.max_iter), 1))
        self._per_chunk = options.cost_every == "chunk"
        if self._per_chunk:
            # both halves of the per-chunk contract, or the driver would
            # silently fall back to evaluating the objective every
            # iteration (see _cost_per_chunk)
            if options.step_fn_cost is None or options.step_fn_light is None:
                raise ValueError(
                    'cost_every="chunk" requires step_fn_cost (a '
                    "standalone objective over the post-iteration "
                    "state) AND step_fn_light (the cost-free step the "
                    "scan body runs)")
            self.cost_every = 1
        else:
            if options.step_fn_cost is not None:
                raise ValueError(
                    "step_fn_cost is only consumed by the per-chunk "
                    'objective mode — pass cost_every="chunk" with it, '
                    f"not cost_every={options.cost_every!r} (which "
                    f"would silently ignore it)")
            self.cost_every = max(int(options.cost_every), 1)
        self.log = RunLog()
        # RecoveryReport from the last supervised run (None when
        # resilience is off or run() has not executed yet)
        self.recovery = None
        self._compiled: Dict[int, Callable] = {}

    # ------------------------------------------------------ compilation
    def _scan_step(self, k: int) -> Callable:
        """Fused K-iteration step, compiled once per distinct chunk
        length (the tail chunk of a run compiles a second, shorter
        program)."""
        if k not in self._compiled:
            if self._cost_per_chunk:
                self._compiled[k] = make_chunk_cost_step(
                    self.step_fn_light, self.step_fn_cost, self.bundle,
                    chunk=k, update_replicated=self.update_replicated)
            else:
                self._compiled[k] = make_scan_step(
                    self.step_fn, self.bundle, chunk=k,
                    update_replicated=self.update_replicated,
                    fn_light=self.step_fn_light,
                    cost_every=self.cost_every,
                    light_updates_replicated=self.light_updates_replicated)
        return self._compiled[k]

    @property
    def step(self) -> Callable:
        """The per-iteration compiled step (chunk=1 legacy path)."""
        if "per_step" not in self._compiled:
            self._compiled["per_step"] = make_step(self.step_fn,
                                                   self.bundle)
        return self._compiled["per_step"]

    @property
    def _light_step(self) -> Callable:
        """Cost-free per-iteration step (chunk=1 path, off-grid
        iterations of ``cost_every``).  When the light step feeds the
        broadcast update (``light_updates_replicated``) it already has
        the ``(data', out)`` shape ``make_step`` expects; otherwise wrap
        its bare data return with a dummy scalar."""
        if "per_step_light" not in self._compiled:
            fn_light = self.step_fn_light
            if self.light_updates_replicated:
                light = fn_light
            else:
                def light(d, rep, axes):
                    return fn_light(d, rep, axes), jnp.float32(0.0)

            self._compiled["per_step_light"] = make_step(light,
                                                         self.bundle)
        return self._compiled["per_step_light"]

    # ----------------------------------------------------- convergence
    def _converged(self) -> bool:
        if not self.tol:
            return False
        c = self.log.costs
        # when cost skipping is active the log repeats each evaluated
        # objective; compare costs cost_window *evaluations* apart
        stride = (self.chunk if self._cost_per_chunk
                  else self.cost_every if self._skips_cost else 1)
        w = self.cost_window * stride
        if len(c) <= w:
            return False
        prev, cur = c[-w - 1], c[-1]
        return abs(prev - cur) <= self.tol * max(abs(prev), 1e-12)

    # ------------------------------------------------------ sanitizers
    def _last_init(self):
        """Initial value of the carried last-output slot (``None`` when
        the mode carries no extra output between chunks)."""
        return (init_cost_like(self.step_fn_cost, self.bundle)
                if self._cost_per_chunk
                else init_out_like(self.step_fn, self.bundle)
                if self._skips_cost else None)

    def _assert_contracts(self, start_iter: int) -> None:
        """checks=True pre-flight (repro.core.checks): the initial
        state is finite and the compiled step's carry is structure/
        shape/dtype-stable — the latter via ``jax.eval_shape``, so
        nothing is dispatched before the verdict."""
        data, rep = self.bundle.data, self.bundle.replicated
        _checks.assert_all_finite(
            {"data": data, "replicated": rep}, "initial bundle state")
        if self.chunk == 1:
            spec = _checks.eval_step_spec(self.step, data, rep)
            _checks.assert_carry_stable(
                self.step, data, spec[0], "per-step data carry")
            return
        k = min(self.chunk, max(self.max_iter - start_iter, 1))
        step = self._scan_step(k)
        last = self._last_init()
        if last is not None:
            spec = _checks.eval_step_spec(step, data, rep,
                                          np.int32(start_iter), last)
        else:
            spec = _checks.eval_step_spec(step, data, rep,
                                          np.int32(start_iter))
        _checks.assert_carry_stable(
            step, (data, rep), (spec[0], spec[1]), "chunked scan carry")

    # ------------------------------------------------------------- run
    def run(self, start_iter: int = 0) -> Bundle:
        if self.checks:
            self._assert_contracts(start_iter)
        if self.chunk == 1 and self.options.resilience is None:
            return self._run_per_step(start_iter)
        # supervised runs always take the chunked loop: its chunk-boundary
        # host sync is where snapshots, validation and rollback live, and
        # make_scan_step(chunk=1) reproduces per-step semantics exactly
        return self._run_chunked(start_iter)

    @property
    def _skips_cost(self) -> bool:
        return self.cost_every > 1 and self.step_fn_light is not None

    @property
    def _cost_per_chunk(self) -> bool:
        """Chunk-granular objective (``engine.make_chunk_cost_step``):
        the scan runs only the cost-free step and the objective is
        evaluated once per dispatch, on the chunk's final state.  Keyed
        on the *requested* ``cost_every="chunk"`` (an integer cadence
        with a step_fn_cost present must honor the integer, not switch
        modes); per-step runs (chunk=1) evaluate every iteration
        anyway, so they use the plain path."""
        return self._per_chunk and self.chunk > 1

    def _progress_event(self, start: int, k: int, dt: float) -> dict:
        """One chunk-boundary progress event (``RunOptions.progress_fn``,
        DESIGN.md §20): iteration range just completed, the newest
        evaluated objective, wall time, and the convergence verdict."""
        return {"kind": "chunk", "start": int(start), "iters": int(k),
                "done": int(start + k),
                "cost": (self.log.costs[-1] if self.log.costs else None),
                "dt_s": float(dt),
                "converged_at": self.log.converged_at}

    def _dispatch_chunk(self, data, rep, last, i: int, k: int):
        """One fused-chunk dispatch + its host sync, as a unit the
        resilience supervisor can retry (the ``dispatch`` chaos fault
        point lives here, so injected failures tick per attempt)."""
        _chaos.maybe_raise("dispatch", step=i)
        with _chunk_span("dispatch", i):
            if self._cost_per_chunk or self._skips_cost:
                data, rep, last, trace = self._scan_step(k)(
                    data, rep, np.int32(i), last)
            else:
                data, rep, trace = self._scan_step(k)(data, rep,
                                                      np.int32(i))
        return data, rep, last, _costs_to_host(trace, i)

    def _run_chunked(self, start_iter: int) -> Bundle:
        data, rep = self.bundle.data, self.bundle.replicated
        last = self._last_init()
        sup = None
        if self.options.resilience is not None:
            from repro.resilience.supervisor import Supervisor
            sup = Supervisor(self.options.resilience, self.bundle,
                             start_iter=start_iter,
                             last_init=self._last_init)
        ema = None
        compiled_ks = set()
        i = start_iter
        while i < self.max_iter:
            k = min(self.chunk, self.max_iter - i)
            first_call = k not in compiled_ks
            compiled_ks.add(k)
            with _chunk_span("", i, first_call=int(first_call)):
                t0 = time.perf_counter()
                if sup is not None:
                    sup.begin_chunk(data, rep, last, i,
                                    len(self.log.costs))
                    try:
                        data, rep, last, costs = sup.dispatch(
                            self._dispatch_chunk, data, rep, last, i, k)
                        if _chaos.is_active():  # silent-corruption injector
                            data = _chaos.poison_tree("carry_nan", data,
                                                      step=i)
                        sup.validate(data, rep, costs, i + k - 1)
                    except DivergenceError as e:
                        sup.report.wall_time_lost_s += \
                            time.perf_counter() - t0
                        data, rep, last, i = sup.rollback(e, self.log)
                        ema = None  # timings across a rollback don't compare
                        continue
                else:
                    data, rep, last, costs = self._dispatch_chunk(
                        data, rep, last, i, k)
                    if _chaos.is_active():
                        data = _chaos.poison_tree("carry_nan", data,
                                                  step=i)
                dt = time.perf_counter() - t0
                with _chunk_span("host", i):
                    if self.checks:
                        _checks.assert_costs_finite(
                            costs, f"chunk ending at iteration {i + k - 1}")
                        _checks.assert_all_finite(
                            {"data": data, "replicated": rep},
                            f"state after iteration {i + k - 1}")
                    self.log.times.extend([dt / k] * k)
                    self.log.costs.extend(float(c) for c in np.ravel(costs))
                    # a chunk length's first dispatch includes XLA
                    # compilation (e.g. the shorter tail program) — keep
                    # it out of the straggler watchdog and its EMA
                    if not first_call:
                        if ema is not None and \
                                dt > self.straggler_factor * ema:
                            self.log.straggler_steps.append(i)
                            if self.checkpoint_fn is not None:
                                self.checkpoint_fn(self.bundle.with_data(
                                    data, replicated=rep), i + k - 1)
                        ema = dt if ema is None else 0.9 * ema + 0.1 * dt
                    if (self.checkpoint_every
                            and self.checkpoint_fn is not None
                            and (i + k) // self.checkpoint_every
                            > i // self.checkpoint_every):
                        self.checkpoint_fn(self.bundle.with_data(
                            data, replicated=rep), i + k - 1)
                    # a local verdict, not `converged_at is not None`: a
                    # rerun of a warmed driver (benchmarks' timed_round)
                    # must not break on a previous run's convergence record
                    conv = self._converged()
                i += k
                if conv:
                    self.log.converged_at = i - 1
                if self.progress_fn is not None:
                    with _chunk_span("progress", i - k):
                        ctl = self.progress_fn(
                            self._progress_event(i - k, k, dt))
                    # only a dict return is a control signal — callbacks
                    # that happen to return something else (a logging
                    # listcomp, an appended list) must stay inert
                    if isinstance(ctl, dict) and ctl.get("stop"):
                        self.log.cancelled_at = i - 1
                        break
                if conv:
                    break
        # accumulate across reruns of a warmed driver, mirroring the
        # batched driver's per-instance counter
        self.log.iters_run = (self.log.iters_run or 0) + (i - start_iter)
        if sup is not None:
            self.recovery = sup.finalize()
        return self.bundle.with_data(data, replicated=rep)

    def _run_per_step(self, start_iter: int) -> Bundle:
        data, rep = self.bundle.data, self.bundle.replicated
        ema = None
        n_done = 0
        for i in range(start_iter, self.max_iter):
            t0 = time.perf_counter()
            if _chaos.is_active():  # unsupervised: a fault kills the run
                _chaos.maybe_raise("dispatch", step=i)
            if self._skips_cost and i % self.cost_every != 0:
                # off the cost grid: run the objective-free step and
                # carry the last evaluated cost forward
                data, aux = self._light_step(data, rep)
                if self.light_updates_replicated and \
                        self.update_replicated is not None:
                    rep = self.update_replicated(rep, aux)
                jax.block_until_ready(jax.tree.leaves(data)[0])
                dt = time.perf_counter() - t0
                self.log.times.append(dt)
                self.log.costs.append(self.log.costs[-1]
                                      if self.log.costs else float("inf"))
            else:
                data, out = self.step(data, rep)
                cost = out["cost"] if isinstance(out, dict) else out
                cost = cost.block_until_ready()
                dt = time.perf_counter() - t0
                self.log.times.append(dt)
                cost_val = float(np.asarray(jax.device_get(cost)))
                if self.checks:
                    _checks.assert_costs_finite(
                        np.asarray([cost_val]), f"iteration {i}")
                    _checks.assert_all_finite(
                        {"data": data}, f"state after iteration {i}")
                self.log.costs.append(cost_val)
                if self.update_replicated is not None:
                    rep = self.update_replicated(rep, out)
            if _chaos.is_active():
                data = _chaos.poison_tree("carry_nan", data, step=i)
            # straggler watchdog: a step far beyond the EMA is logged and
            # (in multi-host deployment) triggers an early checkpoint
            if ema is not None and dt > self.straggler_factor * ema:
                self.log.straggler_steps.append(i)
                if self.checkpoint_fn is not None:
                    self.checkpoint_fn(
                        self.bundle.with_data(data, replicated=rep), i)
            ema = dt if ema is None else 0.9 * ema + 0.1 * dt
            if (self.checkpoint_every and self.checkpoint_fn is not None
                    and (i + 1) % self.checkpoint_every == 0):
                self.checkpoint_fn(
                    self.bundle.with_data(data, replicated=rep), i)
            n_done += 1
            conv = self._converged()
            if conv:
                self.log.converged_at = i
            if self.progress_fn is not None:
                ctl = self.progress_fn(self._progress_event(i, 1, dt))
                if isinstance(ctl, dict) and ctl.get("stop"):
                    self.log.cancelled_at = i
                    break
            if conv:
                break
        self.log.iters_run = (self.log.iters_run or 0) + n_done
        return self.bundle.with_data(data, replicated=rep)


# --------------------------------------------------------------------
# Batched multi-instance execution (solve_many, DESIGN.md §19)
# --------------------------------------------------------------------


class BatchedDriver:
    """Drive one *bucket* of stacked instances to per-instance
    convergence.

    Same knobs as :class:`IterativeDriver` (one ``RunOptions``), same
    chunked loop — but the carry is the batched state ``{"d", "r"
    [, "last"]}`` (every leaf leading with the instance axis B) plus a
    bucket-shared replicated tree, and convergence/logging/early exit
    are per instance:

    - each instance gets its own :class:`RunLog` (costs, times,
      ``converged_at``, ``iters_run``);
    - a converged instance's lane freezes via the active mask
      (``engine.freeze_where``) and stops accruing ``iters_run`` —
      frozen lanes still occupy device FLOPs until re-compaction;
    - when the active fraction drops below ``recompact_below`` the
      bucket re-compacts: retired lanes spill to host, live lanes
      re-stack into a smaller program (the jitted step retraces once
      per distinct batch size — at most ``log2(B)`` recompiles);
    - checkpoints always use the *full-bucket* layout
      (:meth:`snapshot_payload` scatters the compacted state + retired
      spills back to B0 rows), so restore is independent of when
      compaction happened;
    - ``RunOptions.resilience`` wraps each dispatch in the same
      classify → bounded-retry → ring-then-disk rollback discipline as
      the single-instance ``Supervisor``, with snapshots extended to
      the batch bookkeeping (:class:`_BatchSupervisor`).

    ``orig_indices`` maps each stacked row to its position in the
    caller's instance list; ``-1`` marks mesh-alignment filler lanes
    (duplicated data, inactive from the start, never reported).
    """

    def __init__(self, step_fn: Callable, bundle: Bundle, *,
                 options: Optional[RunOptions] = None,
                 orig_indices=None, recompact_below: float = 0.5):
        self.options = options = options or RunOptions()
        self.step_fn = step_fn
        self.step_fn_light = options.step_fn_light
        self.step_fn_cost = options.step_fn_cost
        self.update_replicated = options.update_replicated
        self.light_updates_replicated = options.light_updates_replicated
        self.max_iter = options.max_iter
        self.tol = options.tol
        self.cost_window = options.cost_window
        self.checkpoint_fn = options.checkpoint_fn
        self.checks = options.checks
        self.progress_fn = options.progress_fn
        self.chunk = max(min(int(options.chunk),
                             max(int(options.max_iter), 1)), 1)
        self.checkpoint_every = options.checkpoint_every
        if self.checkpoint_every:
            self.checkpoint_every = min(int(self.checkpoint_every),
                                        max(int(options.max_iter), 1))
        self._per_chunk = options.cost_every == "chunk"
        if self._per_chunk:
            if options.step_fn_cost is None or options.step_fn_light is None:
                raise ValueError(
                    'cost_every="chunk" requires step_fn_cost AND '
                    "step_fn_light (see IterativeDriver)")
            self.cost_every = 1
        else:
            if options.step_fn_cost is not None:
                raise ValueError(
                    "step_fn_cost is only consumed by the per-chunk "
                    'objective mode — pass cost_every="chunk" with it')
            self.cost_every = max(int(options.cost_every), 1)
        self.recompact_below = float(recompact_below)

        state = dict(bundle.data)
        if set(state) != {"d", "r"}:
            raise ValueError(
                f'BatchedDriver expects bundle.data == {{"d", "r"}} '
                f"(batched data + batched replicated), got "
                f"{sorted(state)}")
        B = jax.tree.leaves(state["d"])[0].shape[0]
        if self._cost_per_chunk:
            state["last"] = init_batched_cost_like(
                self.step_fn_cost, state, bundle.replicated)
        elif self._skips_cost:
            state["last"] = init_batched_out_like(
                self.step_fn, state, bundle.replicated)
        self.bundle = bundle.with_data(state)
        self.state = state
        self.B0 = B
        self.orig = (np.asarray(orig_indices, dtype=np.int64)
                     if orig_indices is not None
                     else np.arange(B, dtype=np.int64))
        if len(self.orig) != B:
            raise ValueError(
                f"orig_indices has {len(self.orig)} entries for a "
                f"batch of {B}")
        # bookkeeping lives in full-layout row space [0, B0); ``slots``
        # maps the current compacted position s -> row slots[s]
        self.slots = np.arange(B, dtype=np.int64)
        self.active = self.orig >= 0
        self.iters_run = np.zeros(B, np.int64)
        self.converged_at = np.full(B, -1, np.int64)
        self.logs = [RunLog(iters_run=0) for _ in range(B)]
        self.retired: Dict[int, Any] = {}    # row -> host instance state
        self.recovery = None
        self._iters_at_start = self.iters_run.copy()
        self._compiled: Dict[int, Callable] = {}

    # ------------------------------------------------------ compilation
    @property
    def _skips_cost(self) -> bool:
        return self.cost_every > 1 and self.step_fn_light is not None

    @property
    def _cost_per_chunk(self) -> bool:
        return self._per_chunk and self.chunk > 1

    def _scan_step(self, k: int) -> Callable:
        """Batched fused step, compiled once per chunk length; batch-
        size changes from re-compaction retrace inside the same jit."""
        if k not in self._compiled:
            if self._cost_per_chunk:
                self._compiled[k] = make_batched_chunk_cost_step(
                    self.step_fn_light, self.step_fn_cost, self.bundle,
                    self.state, chunk=k,
                    update_replicated=self.update_replicated)
            else:
                self._compiled[k] = make_batched_scan_step(
                    self.step_fn, self.bundle, self.state, chunk=k,
                    update_replicated=self.update_replicated,
                    fn_light=self.step_fn_light,
                    cost_every=self.cost_every,
                    light_updates_replicated=self.light_updates_replicated)
        return self._compiled[k]

    # ------------------------------------------------------ convergence
    def _converged_log(self, log: RunLog) -> bool:
        if not self.tol:
            return False
        c = log.costs
        stride = (self.chunk if self._cost_per_chunk
                  else self.cost_every if self._skips_cost else 1)
        w = self.cost_window * stride
        if len(c) <= w:
            return False
        prev, cur = c[-w - 1], c[-1]
        return abs(prev - cur) <= self.tol * max(abs(prev), 1e-12)

    # -------------------------------------------------------- dispatch
    def _dispatch_chunk(self, state, mask, i: int, k: int):
        _chaos.maybe_raise("dispatch", step=i)
        with _chunk_span("dispatch", i):
            state, trace = self._scan_step(k)(
                state, self.bundle.replicated, mask, np.int32(i))
        return state, _costs_to_host(trace, i)   # costs: (k, B_current)

    def _log_chunk(self, costs, dt: float, i: int, k: int) -> None:
        per = dt / max(k, 1)
        for s, row in enumerate(self.slots):
            row = int(row)
            if not self.active[row]:
                continue
            log = self.logs[row]
            log.costs.extend(float(c) for c in costs[:, s])
            log.times.extend([per] * k)
            self.iters_run[row] += k
            log.iters_run = int(self.iters_run[row])
            if self._converged_log(log):
                self.active[row] = False
                self.converged_at[row] = i + k - 1
                log.converged_at = i + k - 1

    def _progress_event(self, start: int, k: int, dt: float) -> dict:
        """Chunk-boundary progress event with a per-instance section
        keyed by the caller's original instance index.  Lanes retired by
        re-compaction no longer appear — their final state was already
        reported in the chunk event that marked them converged."""
        inst = {}
        for row in self.slots:
            row = int(row)
            j = int(self.orig[row])
            if j < 0:
                continue                         # mesh-alignment filler
            log = self.logs[row]
            inst[j] = {"cost": (log.costs[-1] if log.costs else None),
                       "iters_run": int(self.iters_run[row]),
                       "converged_at": (int(self.converged_at[row])
                                        if self.converged_at[row] >= 0
                                        else None)}
        return {"kind": "chunk", "start": int(start), "iters": int(k),
                "done": int(start + k), "dt_s": float(dt),
                "instances": inst}

    def _apply_control(self, ctl: dict, it: int) -> None:
        """Apply a ``progress_fn`` control return (§21): freeze the
        named original-index instances' lanes at this chunk boundary
        exactly like converged ones — deactivated here, retired by the
        ``_maybe_recompact`` pass that follows the progress callback —
        so sibling lanes' trajectories are unperturbed.  ``stop`` ends
        the whole bucket (every still-active lane is cancelled)."""
        if ctl.get("stop"):
            targets = [int(j) for j in self.orig if j >= 0]
        else:
            targets = [int(j) for j in (ctl.get("cancel_instances")
                                        or ())]
        for j in targets:
            rows = np.flatnonzero(self.orig == j)
            if rows.size == 0:
                continue
            row = int(rows[0])
            if not self.active[row]:
                continue
            self.active[row] = False
            self.logs[row].cancelled_at = it

    # ---------------------------------------------------- re-compaction
    def _maybe_recompact(self) -> None:
        cur = self.active[self.slots]
        n_act = int(cur.sum())
        B = len(self.slots)
        if n_act == 0 or n_act >= self.recompact_below * B:
            return
        keep = np.flatnonzero(cur)
        parts = max(self.bundle.n_partitions, 1)
        if parts > 1:
            need = (-len(keep)) % parts
            if need:
                # keep some frozen lanes as filler so the batch axis
                # stays divisible across the mesh
                frozen = np.flatnonzero(~cur)[:need]
                keep = np.sort(np.concatenate([keep, frozen]))
        if len(keep) == B:
            return
        host = _persist.to_host(self.state)
        keep_set = set(keep.tolist())
        for s in range(B):
            if s not in keep_set:
                self.retired[int(self.slots[s])] = jax.tree.map(
                    lambda x, _s=s: x[_s], host)
        compact = jax.tree.map(lambda x: x[keep], host)
        self.state = _persist.readmit_batched(self.bundle, compact)
        self.bundle = self.bundle.with_data(self.state)
        self.slots = self.slots[keep]

    # ------------------------------------------------------ checkpoints
    def payload_template(self) -> Dict[str, Any]:
        """Shape/tree template of :meth:`snapshot_payload` — hand it to
        ``checkpoint.checkpointer.restore`` as ``like``.  The state side
        is always the full B0-row layout, so the template is independent
        of the current compaction."""
        full = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (self.B0,) + tuple(x.shape[1:]), x.dtype), self.state)
        return {"state": full,
                "batch": {"active": np.zeros(self.B0, bool),
                          "iters_run": np.zeros(self.B0, np.int64),
                          "converged_at": np.zeros(self.B0, np.int64)}}

    def snapshot_payload(self) -> Dict[str, Any]:
        """Full-bucket checkpoint payload: the compacted device state
        scattered back to B0 rows, retired host spills filled in, plus
        the per-instance bookkeeping arrays."""
        host = _persist.to_host(self.state)
        full = _persist.scatter_batched(host, self.slots, self.B0)
        for row, inst in self.retired.items():
            _persist.set_instance(full, row, inst)
        return {"state": full,
                "batch": {"active": self.active.copy(),
                          "iters_run": self.iters_run.copy(),
                          "converged_at": self.converged_at.copy()}}

    def load_payload(self, payload, *, rewind_logs: bool = False) -> None:
        """Adopt a full-layout payload: resume (fresh logs from the
        restored boundary, mirroring single-instance resume) or mid-run
        disk rollback (``rewind_logs=True`` truncates each lane's log to
        the iterations it had logged at the checkpoint)."""
        batch = payload["batch"]
        iters = np.asarray(jax.device_get(batch["iters_run"]),
                           dtype=np.int64)
        conv = np.asarray(jax.device_get(batch["converged_at"]),
                          dtype=np.int64)
        act = np.asarray(jax.device_get(batch["active"])).astype(bool)
        if rewind_logs:
            base = self._iters_at_start
            for row in range(self.B0):
                n = int(max(iters[row] - base[row], 0))
                log = self.logs[row]
                del log.costs[n:]
                del log.times[n:]
                log.iters_run = int(iters[row])
                log.converged_at = (int(conv[row]) if conv[row] >= 0
                                    else None)
        else:
            self.logs = [RunLog(iters_run=int(iters[r]),
                                converged_at=(int(conv[r])
                                              if conv[r] >= 0 else None))
                         for r in range(self.B0)]
        self.active, self.iters_run, self.converged_at = act, iters, conv
        self.slots = np.arange(self.B0, dtype=np.int64)
        self.retired = {}
        self.state = _persist.readmit_batched(self.bundle,
                                              payload["state"])
        self.bundle = self.bundle.with_data(self.state)

    # ---------------------------------------------------------- results
    def host_states(self) -> Dict[int, Any]:
        """Per-row final instance states (host): current lanes sliced
        out of the device state, retired lanes from their spills."""
        host = _persist.to_host(self.state)
        out = dict(self.retired)
        for s, row in enumerate(self.slots):
            out[int(row)] = jax.tree.map(lambda x, _s=s: x[_s], host)
        return out

    # ------------------------------------------------------------- run
    def run(self, start_iter: int = 0) -> "BatchedDriver":
        self._iters_at_start = self.iters_run.copy()
        sup = None
        if self.options.resilience is not None:
            sup = _BatchSupervisor(self.options.resilience, self)
        i = start_iter
        # the program compiles once per (chunk length, batch size):
        # re-compaction's smaller batches compile too
        compiled = set()
        while i < self.max_iter and bool(self.active.any()):
            k = min(self.chunk, self.max_iter - i)
            first_call = (k, len(self.slots)) not in compiled
            compiled.add((k, len(self.slots)))
            mask = jnp.asarray(self.active[self.slots])
            with _chunk_span("", i, first_call=int(first_call)):
                t0 = time.perf_counter()
                if sup is not None:
                    sup.begin_chunk(i)
                    try:
                        state, costs = sup.dispatch(
                            self._dispatch_chunk, self.state, mask, i, k)
                        if _chaos.is_active():
                            state = dict(state, d=_chaos.poison_tree(
                                "carry_nan", state["d"], step=i))
                        sup.validate(state, costs, i + k - 1)
                    except DivergenceError as e:
                        sup.report.wall_time_lost_s += \
                            time.perf_counter() - t0
                        i = sup.rollback(e)
                        continue
                else:
                    state, costs = self._dispatch_chunk(
                        self.state, mask, i, k)
                    if _chaos.is_active():
                        state = dict(state, d=_chaos.poison_tree(
                            "carry_nan", state["d"], step=i))
                self.state = state
                self.bundle = self.bundle.with_data(state)
                dt = time.perf_counter() - t0
                with _chunk_span("host", i):
                    if self.checks:
                        _checks.assert_costs_finite(
                            costs,
                            f"bucket chunk ending at iteration {i + k - 1}")
                        _checks.assert_all_finite(
                            {"data": state["d"], "replicated": state["r"]},
                            f"bucket state after iteration {i + k - 1}")
                    self._log_chunk(costs, dt, i, k)
                    if (self.checkpoint_every
                            and self.checkpoint_fn is not None
                            and (i + k) // self.checkpoint_every
                            > i // self.checkpoint_every):
                        self.checkpoint_fn(self.snapshot_payload(),
                                           i + k - 1)
                i += k
                if self.progress_fn is not None:
                    with _chunk_span("progress", i - k):
                        ctl = self.progress_fn(
                            self._progress_event(i - k, k, dt))
                    if isinstance(ctl, dict):
                        self._apply_control(ctl, i - 1)
                self._maybe_recompact()
        if sup is not None:
            self.recovery = sup.finalize()
        return self


class _BatchSupervisor:
    """Retry/rollback supervision for one solve_many bucket.

    The single-instance ``Supervisor`` snapshots ``(data, rep, last)``
    and rewinds one RunLog; a bucket's recovery state additionally
    spans the active mask, per-instance counters and logs, the slot
    map, and the retired spills — so the batched driver carries its own
    snapshot ring with the same classify → bounded-retry →
    ring-then-disk rollback discipline (DESIGN.md §18/§19).  Disk
    fallback restores the full-bucket checkpoint layout written by
    :meth:`BatchedDriver.snapshot_payload`.
    """

    def __init__(self, cfg: ResilienceConfig, driver: BatchedDriver):
        from repro.kernels import common as _kcommon
        self.cfg = cfg
        self.driver = driver
        self.report = RecoveryReport()
        self.ring: deque = deque(maxlen=cfg.ring)
        # mirror Supervisor: the chaos seed wins during a drill so
        # recovery reports replay deterministically
        _seed = _chaos.active_seed()
        self.rng = np.random.default_rng(cfg.seed if _seed is None
                                         else _seed)
        self._rollbacks_done = 0
        self._last_restored_it: Optional[int] = None
        self._kernel_baseline = len(_kcommon.kernel_fallbacks())

    # ------------------------------------------------------- snapshots
    def begin_chunk(self, it: int) -> None:
        d = self.driver
        self.ring.append({
            "it": it,
            "state": _persist.to_host(d.state),
            "slots": d.slots.copy(), "active": d.active.copy(),
            "iters": d.iters_run.copy(), "conv": d.converged_at.copy(),
            "logs_len": [len(log.costs) for log in d.logs],
            "retired": dict(d.retired)})

    def _restore(self, snap) -> int:
        d = self.driver
        d.slots = snap["slots"].copy()
        d.active = snap["active"].copy()
        d.iters_run = snap["iters"].copy()
        d.converged_at = snap["conv"].copy()
        d.retired = dict(snap["retired"])
        for row in range(d.B0):
            log = d.logs[row]
            n = snap["logs_len"][row]
            del log.costs[n:]
            del log.times[n:]
            log.iters_run = int(d.iters_run[row])
            log.converged_at = (int(d.converged_at[row])
                                if d.converged_at[row] >= 0 else None)
        d.state = _persist.readmit_batched(d.bundle, snap["state"])
        d.bundle = d.bundle.with_data(d.state)
        return snap["it"]

    def _exhausted(self, msg: str):
        """Budget-exhaustion error carrying the recovery ledger, so the
        serving quarantine path (§21) can attach it per request."""
        from repro.resilience.errors import ResilienceExhausted
        err = ResilienceExhausted(msg)
        err.report = self.finalize()
        return err

    # --------------------------------------------------------- dispatch
    def dispatch(self, fn: Callable, state, mask, i: int, k: int):
        from repro.resilience.errors import classify
        attempt = 0
        while True:
            t0 = time.perf_counter()
            try:
                return fn(state, mask, i, k)
            except Exception as e:
                kind = classify(e, self.cfg.transient_types)
                self.report.record_fault("dispatch", i, e)
                self.report.wall_time_lost_s += time.perf_counter() - t0
                if kind != "transient":
                    raise
                if attempt >= self.cfg.max_retries:
                    raise self._exhausted(
                        f"bucket chunk dispatch at iteration {i} still "
                        f"failing after {attempt} retries: {e}") from e
                t1 = time.perf_counter()
                self.report.retries += 1
                time.sleep(self._backoff(attempt))
                # the failed call may have consumed the donated state
                state = _persist.readmit_batched(
                    self.driver.bundle, self.ring[-1]["state"])
                self.report.wall_time_lost_s += time.perf_counter() - t1
                attempt += 1

    def _backoff(self, attempt: int) -> float:
        base = self.cfg.backoff_s * self.cfg.backoff_factor ** attempt
        return base * (1.0 + self.cfg.jitter
                       * float(self.rng.uniform(-1.0, 1.0)))

    # ------------------------------------------------------- divergence
    def validate(self, state, costs, it: int) -> None:
        try:
            _checks.assert_costs_finite(
                costs, f"resilience: bucket chunk ending at "
                       f"iteration {it}")
            _checks.assert_all_finite(
                {"data": state["d"], "replicated": state["r"]},
                f"resilience: bucket state after iteration {it}")
        except _checks.CheckError as e:
            raise DivergenceError(str(e), step=it) from e

    def rollback(self, err: DivergenceError) -> int:
        self.report.record_fault("divergence", err.step, err)
        if self._rollbacks_done >= self.cfg.max_rollbacks:
            raise self._exhausted(
                f"rollback budget ({self.cfg.max_rollbacks}) exhausted; "
                f"latest divergence: {err}") from err
        self._rollbacks_done += 1
        self.report.rollbacks += 1
        t0 = time.perf_counter()
        # same-boundary walk-back (see Supervisor.rollback): restoring
        # the boundary that already diverged once would replay the same
        # divergence unless a rescale hook perturbs it
        if (self.ring and self.cfg.rollback_rescale is None
                and self.ring[-1]["it"] == self._last_restored_it):
            self.ring.pop()
        if self.ring:
            it = self._restore(self.ring.pop())
        else:
            it = self._restore_from_disk(err)
        self._last_restored_it = it
        if self.cfg.rollback_rescale is not None:
            d = self.driver
            d.state = dict(d.state, r=self.cfg.rollback_rescale(
                d.state["r"], self._rollbacks_done))
            d.bundle = d.bundle.with_data(d.state)
        self.report.wall_time_lost_s += time.perf_counter() - t0
        return it

    def _restore_from_disk(self, err: DivergenceError) -> int:
        if self.cfg.checkpoint_dir is None:
            raise self._exhausted(
                "snapshot ring exhausted and no checkpoint_dir to fall "
                "back to; latest divergence: " + str(err)) from err
        from repro.checkpoint import checkpointer as ckpt
        step, _skipped = ckpt.latest_valid_step(self.cfg.checkpoint_dir)
        if step is None:
            raise self._exhausted(
                f"snapshot ring exhausted and no valid checkpoint under "
                f"{self.cfg.checkpoint_dir!r}; latest divergence: {err}"
            ) from err
        payload, _ = ckpt.restore(self.cfg.checkpoint_dir, step,
                                  self.driver.payload_template())
        self.driver.load_payload(payload, rewind_logs=True)
        self.report.checkpoint_restores += 1
        return step

    # --------------------------------------------------------- wrap-up
    def finalize(self):
        from repro.kernels import common as _kcommon
        events = _kcommon.kernel_fallbacks()[self._kernel_baseline:]
        self.report.kernel_fallbacks = [dict(e) for e in events]
        return self.report
