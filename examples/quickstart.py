"""Quickstart: declare a workload once, solve() it — in ~40 lines.

The paper's driver program (configure -> parallelize -> iterate) is
generic; a workload is ONE `Problem` declaration (DESIGN.md §14): how to
build the co-partitioned bundle, and what one map/reduce learning
iteration does.  Everything else — chunked on-device scans, broadcast
carries, convergence tracking, checkpoint hooks — is derived by
`solve()`.  Here: ridge regression by distributed gradient descent.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core.bundle import Bundle
from repro.core.problem import Problem, solve
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import smallest_mesh


class RidgeProblem(Problem):
    """The whole workload declaration — this is the paper's claim that
    new analysis tasks are cheap to express on the shared engine."""

    replicated_in_carry = True      # the model w advances every iteration

    def __init__(self, lr: float = 0.05):
        self.lr = lr

    def init_bundle(self, inputs, mesh) -> Bundle:
        X, y = inputs               # co-partitioned over samples
        return Bundle.create(
            {"X": X, "y": y}, mesh=mesh,
            replicated={"w": jnp.zeros(X.shape[1], X.dtype)})

    def full_step(self, d, rep, axes):
        r = d["X"] @ rep["w"] - d["y"]
        grad = d["X"].T @ r
        cost = 0.5 * jnp.sum(r ** 2)
        n = jnp.float32(d["X"].shape[0])
        if axes:                    # map -> psum reduce, no driver trip
            grad = jax.lax.psum(grad, axes)
            cost = jax.lax.psum(cost, axes)
            n = jax.lax.psum(n, axes)   # global row count, so the step
        w_new = rep["w"] - self.lr * grad / n   # size is mesh-invariant
        return d, {"cost": cost, "w": w_new}

    def refresh_replicated(self, rep, out):
        return dict(rep, w=out["w"])

    def finalize(self, bundle, log):
        return jax.device_get(bundle.replicated["w"]), {}


def main():
    key = jax.random.PRNGKey(0)
    n, d = 4096, 32
    w_true = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    X = jax.random.normal(jax.random.fold_in(key, 2), (n, d))
    y = X @ w_true + 0.01 * jax.random.normal(jax.random.fold_in(key, 3),
                                              (n,))

    sol = solve(RidgeProblem(lr=0.05), X, y, mesh=smallest_mesh(),
                max_iter=200, tol=1e-6, chunk=8)
    err = float(jnp.linalg.norm(sol.x - w_true) /
                jnp.linalg.norm(w_true))
    print(f"bundle: {sol.bundle.n_records} records, "
          f"{sol.bundle.n_partitions} partition(s)")
    print(f"converged at iter {sol.log.converged_at}; "
          f"cost {sol.costs[0]:.1f} -> {sol.costs[-1]:.4f}; "
          f"relative weight error {err:.2e}")
    assert err < 0.05


if __name__ == "__main__":
    enable_compile_cache()
    main()
