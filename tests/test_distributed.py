"""Multi-device semantics tests.

jax locks the device count at first init, so anything needing >1 device
runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8.
Each scenario asserts distributed == single-device math.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def run_sub(body: str, timeout: int = 600):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8 "
            + os.environ.get("XLA_FLAGS", ""))
        import jax, jax.numpy as jnp, numpy as np
        assert len(jax.devices()) == 8
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_bundle_distributed_equals_local():
    run_sub("""
    from repro.core.bundle import Bundle, bundle_map, bundle_map_reduce, gather
    mesh = make_mesh((4, 2), ("data", "model"))
    key = jax.random.PRNGKey(0)
    data = {"a": jax.random.normal(key, (16, 5)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (16, 3))}
    b_loc = Bundle.create(dict(data))
    b_dist = Bundle.create(dict(data), mesh=mesh, axes=("data",))
    assert b_dist.n_partitions == 4
    f = lambda d: {"a": d["a"] * 2 + 1, "b": jnp.tanh(d["b"])}
    out_l = gather(bundle_map(f, b_loc))
    out_d = gather(bundle_map(f, b_dist))
    for k in out_l:
        np.testing.assert_allclose(out_l[k], out_d[k], rtol=1e-6)
    g = lambda d: {"gram": d["a"].T @ d["a"], "s": jnp.sum(d["b"])}
    r_l = bundle_map_reduce(g, b_loc)
    r_d = bundle_map_reduce(g, b_dist)
    np.testing.assert_allclose(np.asarray(r_l["gram"]),
                               np.asarray(r_d["gram"]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(r_l["s"]), float(r_d["s"]), rtol=1e-5)
    print("bundle ok")
    """)


def test_psf_deconvolution_distributed_equals_sequential():
    run_sub("""
    from repro.imaging import psf as psf_op
    from repro.imaging.condat import SolverConfig, solve
    from repro.imaging.deconvolve import deconvolve
    mesh = make_mesh((8,), ("data",))
    data = psf_op.simulate(16, jax.random.PRNGKey(2))
    cfg = SolverConfig(mode="sparse", n_scales=3)
    _, costs = solve(data.Y, data.psfs, cfg, sigma_noise=data.sigma, n_iter=10)
    X, log = deconvolve(data.Y, data.psfs, cfg, mesh=mesh,
                        sigma_noise=data.sigma, max_iter=10, tol=0)
    np.testing.assert_allclose(np.asarray(costs), np.asarray(log.costs),
                               rtol=1e-3)
    print("psf distributed ok")
    """)


def test_scdl_distributed_equals_sequential():
    run_sub("""
    from repro.data.synthetic import coupled_patches
    from repro.imaging.scdl import SCDLConfig, train
    mesh = make_mesh((8,), ("data",))
    S_h, S_l = coupled_patches(256, 25, 9, 16, seed=5)
    cfg = SCDLConfig(n_atoms=16, max_iter=8)
    Xh_s, Xl_s, log_s = train(S_h, S_l, cfg, mesh=None)
    Xh_d, Xl_d, log_d = train(S_h, S_l, cfg, mesh=mesh)
    np.testing.assert_allclose(log_s.costs, log_d.costs, rtol=5e-3)
    np.testing.assert_allclose(Xh_s, Xh_d, rtol=1e-2, atol=1e-3)
    print("scdl distributed ok")

    # ill-conditioned regime: near-duplicate atoms, the factor-once
    # Cholesky/Woodbury broadcast must still give distributed ==
    # sequential (the psum'd outer products feed identical factors)
    rng = np.random.RandomState(9)
    proto_h, proto_l = rng.randn(25, 4), rng.randn(9, 4)
    idx = rng.randint(0, 4, size=256); amp = rng.rand(256) + 0.5
    S_h = jnp.asarray(proto_h[:, idx] * amp
                      + 1e-3 * rng.randn(25, 256), jnp.float32)
    S_l = jnp.asarray(proto_l[:, idx] * amp
                      + 1e-3 * rng.randn(9, 256), jnp.float32)
    Xh_s, _, log_s = train(S_h, S_l, cfg, mesh=None)
    Xh_d, _, log_d = train(S_h, S_l, cfg, mesh=mesh)
    np.testing.assert_allclose(log_s.costs, log_d.costs,
                               rtol=5e-3, atol=1e-3)
    print("scdl ill-conditioned distributed ok")
    """)


def test_hierarchical_psum_and_compression():
    run_sub("""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.parallel.collectives import (CompressedReducer,
                                            hierarchical_psum_local)
    mesh = make_mesh((2, 4), ("pod", "data"))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))

    def flat(xl):
        return jax.lax.psum(jax.lax.psum(xl, "data"), "pod")

    def hier(xl):
        return hierarchical_psum_local(xl, pod_axis="pod", data_axis="data")

    sm = partial(shard_map, mesh=mesh, in_specs=(P(("pod", "data")),),
                 out_specs=P(("pod", "data")), check_vma=False)
    np.testing.assert_allclose(np.asarray(sm(flat)(x)),
                               np.asarray(sm(hier)(x)), rtol=1e-5)

    red = CompressedReducer(mesh)
    def comp(xl):
        e = jnp.zeros_like(xl)
        mean, e2 = red.reduce_local({"g": xl}, {"g": e})
        return mean["g"]
    exact = sm(lambda xl: jax.lax.pmean(jax.lax.pmean(xl, "data"), "pod"))(x)
    approx = sm(comp)(x)
    err = float(jnp.max(jnp.abs(exact - approx)))
    scale = float(jnp.max(jnp.abs(exact)))
    assert err <= 0.02 * max(scale, 1e-6) + 1e-4, (err, scale)
    print("collectives ok")
    """)


def test_pipeline_parallel_matches_sequential():
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from repro.parallel.pipeline import make_pipelined_forward
    mesh = make_mesh((4, 2), ("stage", "data"))
    S_, Lp, D = 4, 2, 16          # 4 stages x 2 layers = 8 layers
    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (S_, Lp, D, D)) * 0.3
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, D))

    def layer_fn(wstack, h):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, h, wstack)
        return h

    # sequential reference over all 8 layers
    ref = x
    for s in range(S_):
        ref = layer_fn(Ws[s], ref)

    fwd = make_pipelined_forward(layer_fn, mesh, n_micro=4,
                                 data_axes=("data",))
    out = fwd(Ws, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("pipeline ok")
    """)


def test_elastic_checkpoint_restore_across_device_counts(tmp_path):
    # save on 8 devices (sharded), restore in THIS 1-device process
    run_sub(f"""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import save
    mesh = make_mesh((8,), ("data",))
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh, P("data")))
    save(r"{tmp_path}", 5, {{"w": w}})
    print("saved")
    """)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.checkpoint import restore
    out, _ = restore(tmp_path, 5, {"w": jnp.zeros((8, 8))})
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(64.0).reshape(8, 8))
