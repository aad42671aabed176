"""Use case (a): space-variant deconvolution of galaxy survey images.

Simulates a Euclid-like stack (stamps + spatially varying anisotropic
PSFs + noise), runs the distributed Algorithm 1 with both regularisers
through the declarative ``solve()`` entry point (DESIGN.md §14), and
reports recovery quality + convergence — the paper's Figs. 4/7 in
miniature.

The solver runs the optimized configuration by default (DESIGN.md §16):
the paired-FFT convolution engine on the derived fast pad (81-grid for
41-px stamps instead of the historical 96), the fused Condat
elementwise kernels, chunked on-device iteration, and — for the sparse
mode — ``cost_every="chunk"``: the scan body is objective-free and the
cost is a weighted reduction of the carried starlet stack evaluated
once per dispatched chunk, exactly the granularity at which convergence
is checked anyway.  ``--per-iter-cost`` switches the observability grid
back to every iteration.

    PYTHONPATH=src python examples/psf_deconvolution.py [--n 512]

Surviving preemption (DESIGN.md §18).  On a preemptible TPU slice, add
checkpointing + supervised execution and rerun the same command after
an eviction — the trajectory continues exactly where it stopped, and
transient in-run failures (worker loss, NaN divergence, torn
checkpoint writes) are retried / rolled back instead of killing the
run::

    from repro.resilience import ResilienceConfig

    sol = solve(DeconvolutionProblem(cfg), data.Y, data.psfs,
                checkpoint_dir="ckpt/psf", checkpoint_every=24,
                resume=True,                # picks the newest VALID step
                resilience=ResilienceConfig(ring=2, max_retries=3))
    print(sol.recovery)      # retries / rollbacks / restores ledger

``resume=True`` falls back past a corrupt newest checkpoint (torn
write during the eviction) with a warning; rollback uses the in-memory
snapshot ring first and the checkpoint directory once the ring is dry.
Fault plans for drills come from the ``REPRO_CHAOS`` env var, e.g.
``REPRO_CHAOS="dispatch@1;carry_nan@2;seed=7"``.

Populations, not stacks (DESIGN.md §19).  Survey traffic is thousands
of small *independent* stamp groups.  Looping ``solve()`` pays trace +
compile + dispatch overhead per group; ``solve_many`` pad-and-buckets
the population by shape into a few stacked programs, runs every bucket
chunked with per-lane masked early exit, and returns one ``Solution``
per instance with its own trajectory (parity with the single solve at
rtol 1e-4 — bit-exact for this workload)::

    from repro.core.problem import solve_many

    instances = [(Y0, psfs0), (Y1, psfs1), ...]   # mixed shapes OK
    sols = solve_many(DeconvolutionProblem(cfg), instances,
                      max_iter=200, tol=1e-5, chunk=12,
                      checkpoint_dir="ckpt/many",   # per-bucket dirs
                      resilience=ResilienceConfig())
    print([s.log.iters_run for s in sols])  # converged lanes run fewer

``benchmarks/bench_many.py`` gates the ≥3x aggregate instances/sec this
buys on 64 mixed-shape stamps (``BENCH_many.json``).
"""
import argparse

import jax
import jax.numpy as jnp

from repro.core.problem import solve
from repro.imaging import psf as psf_op
from repro.imaging.condat import SolverConfig
from repro.imaging.deconvolve import DeconvolutionProblem
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import smallest_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--chunk", type=int, default=12)
    ap.add_argument("--per-iter-cost", action="store_true",
                    help="evaluate the objective every iteration "
                         "instead of once per chunk")
    args = ap.parse_args()

    data = psf_op.simulate(args.n, jax.random.PRNGKey(42))
    mse = lambda a, b: float(jnp.mean((a - b) ** 2))
    print(f"simulated {args.n} stamps; FFT grid "
          f"{psf_op.pad_for(data.Y.shape[-1])}^2 "
          f"(seed hardcoded 96^2); observation MSE vs truth: "
          f"{mse(data.Y, data.X_true):.3e}")

    mesh = smallest_mesh()
    for mode in ("sparse", "lowrank"):
        cfg = SolverConfig(mode=mode, n_scales=4, lam=0.05, rank=16)
        # the sparse objective off the carried starlet stack is pure
        # reduction -> per-chunk observability is effectively free; the
        # low-rank objective needs an SVD, so it stays on the skipping
        # grid instead
        cost_every = (1 if args.per_iter_cost
                      else "chunk" if mode == "sparse" else args.chunk)
        sol = solve(DeconvolutionProblem(cfg, sigma_noise=data.sigma),
                    data.Y, data.psfs, mesh=mesh,
                    max_iter=args.iters, tol=1e-5, chunk=args.chunk,
                    cost_every=cost_every)
        log = sol.log
        # per-chunk observability seeds the trace with +inf until the
        # first evaluation — report from the first evaluated objective
        c0 = next(c for c in log.costs if jnp.isfinite(c))
        print(f"[{mode:7s}] cost_every={cost_every!r:8} "
              f"cost {c0:.3f} -> {log.costs[-1]:.3f} "
              f"in {len(log.costs)} iters "
              f"({log.total_seconds:.1f}s, "
              f"converged_at={log.converged_at}); "
              f"deconvolved MSE: {mse(jnp.asarray(sol.x), data.X_true):.3e}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
