"""Window driver of a batch solve: ``repro.core.problem.solve`` over the
cell's whole population, the program reached through its registry.

The solve runs with ``tol = 0``, an iteration budget it never reaches,
and the program's own chunk length and objective cadence.  Its progress
hook, called at every chunk boundary, opens the measured window once
``open_after_chunks`` chunks have run (the first compiles, or loads its
program from the persistent cache) and closes it at the first boundary
after the run's seconds, where it stops the solve.  ``iter_ms`` is the
window's wall time over the solver iterations completed in it, every
host sync included.  An iteration whose logged objective is not finite
counts as failed.

The check compares what the timed solve produced with
``reference/<problem>.py`` after as many iterations, on inputs made
again from the seed once the solve's state is freed.
"""
from __future__ import annotations

import dataclasses
import math
import time

import generate
from cells import component

BUDGET = 10 ** 9          # iterations the timed solve never reaches


def build_problem(cell, fail):
    """The registered Problem, configured as the cell states; the
    program's config class is reached through the registry."""
    from repro.core import problem as registry
    cls = registry.get(cell.config["problem"])
    base = cls().cfg
    unknown = set(cell.solver) - {f.name for f in dataclasses.fields(base)}
    if unknown:
        fail(f"{cell.name}: the program's config has no {sorted(unknown)}")
    return registry, cls(dataclasses.replace(base, **cell.solver),
                         **cell.problem_args)


def drive(run) -> dict:
    jax, cell, window = run.jax, run.cell, run.window
    registry, problem = build_problem(cell, run.fail)
    ref = component("reference", cell.config["problem"])
    t0 = time.perf_counter()
    key = generate.seed_key(run.seed)
    inputs = jax.block_until_ready(generate.make(cell.config, key))
    t_inputs = time.perf_counter()
    open_after = int(cell.traffic["window"]["open_after_chunks"])
    seen = {"chunks": 0, "failed": 0}

    def progress(ev):
        seen["chunks"] += 1
        if window.opened is None:
            if seen["chunks"] >= open_after:
                window.open(ev["done"])
            return None
        cost = ev.get("cost")
        if cost is None or not math.isfinite(cost):
            seen["failed"] += ev["iters"]
        if not window.due():
            return None
        window.close(ev["done"])
        return {"stop": True}

    sol = registry.solve(problem, *inputs, mesh=run.mesh, max_iter=BUDGET,
                         tol=0.0, progress_fn=progress)
    jax.block_until_ready(sol.x)
    if window.closed is None:
        run.fail("the solve ended before the window closed")
    del inputs
    iters = window.done
    observed = ref.observe(cell, sol, run.seed)
    del sol
    total = window.closed[1]

    def check() -> dict:
        return ref.check(cell, generate.make(cell.config, key), observed,
                         total)

    return {"values": {"iter_ms": window.seconds_open * 1e3 / iters},
            "attempted": iters, "failed": seen["failed"], "units": iters,
            "log": {"iterations": iters,
                    "iterations_before": window.opened[1],
                    "inputs_s": t_inputs - t0,
                    "solve_to_window_s": window.opened[0] - t_inputs},
            "check": check}
