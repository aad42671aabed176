"""On-chip smoke test: the imaging solvers through their user-facing entry
points, at full width, on a TPU.

    python chip_smoke.py             # one chip, phases (a)-(e)
    python chip_smoke.py --chips 4   # four chips: the data-mesh path only

One chip runs five phases, each through ``repro.core.problem.solve`` /
``solve_many`` or the in-process ``repro.serve`` service:

  (a) sparse deconvolution of a survey-scale population of 41x41 stamps;
  (b) low-rank deconvolution of the same population;
  (c) SCDL at the DESIGN.md §13 shapes (P=289, M=81, A=512, K=4096);
  (d) a few deconvolution requests through ``AsyncSolveService``, which
      coalesces them into one ``solve_many`` bucket;
  (e) the ``lowrank`` completion Problem on a stamp-population-sized
      matrix.

Each phase prints one JSON line: the iterations run, the maximum
relative error
``max|x - x_ref| / max|x_ref|`` against a plain float32 reference — the
same solve with every kernel family on its jnp oracle, under
``jax.default_matmul_precision("highest")``, on the same chip — with the
tolerance and the reason for it, ``kernels.common.kernel_fallbacks()``
(must be empty), and for each kernel family of the phase whether it is a
``tpu_custom_call`` in the compiled chunk program.  Completion (e) runs
no kernel, so its reference is the known matrix it completes: the error
is that of the unobserved entries.  The phases built on the randomized
SVT, (b) and (e), also check one SVT on the chip against the exact SVT
in float64 on the host.

``--chips 4`` runs only sparse deconvolution and SCDL over a (4, 1)
data mesh, checks that every data leaf spans the four devices and that
the SCDL program all-reduces across them, and compares each result with
the same inputs solved on one device in the same process.

The last line of standard output is the JSON contract line
``{"ok": true, "device": {...}}``.  The script exits non-zero, and
prints no contract line, when any phase fails, when JAX finds no TPU
or another number of TPU devices than ``--chips``, or when
``REPRO_FORCE_INTERPRET`` is set.  It uses the persistent
compilation cache (``JAX_COMPILATION_CACHE_DIR`` where set, else
``<repo>/.jax_cache``).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import re
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
# full width: a survey-scale stamp population, the GS dictionary shapes
STAMPS = 10000
SERVE_STAMPS = (128, 160, 192, 192)
SCDL_K = 4096
ITERS, CHUNK = 24, 8
FAMILIES_OF = {
    "sparse_deconvolution": ("starlet2d", "condat_elwise"),
    "lowrank_deconvolution": ("condat_elwise",),
    "scdl": ("dict_outer", "admm_elwise"),
    "serve": ("starlet2d", "condat_elwise"),
    "lowrank_completion": (),
}


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(1)


# ---------------------------------------------------------------------
# Observation: the compiled chunk programs
# ---------------------------------------------------------------------

class ChunkPrograms:
    """Records the chunk programs the drivers build (the jitted engine
    steps) with the abstract arguments of their first dispatch, so their
    compiled HLO can be inspected after the run."""

    _FACTORIES = ("make_scan_step", "make_chunk_cost_step",
                  "make_batched_scan_step", "make_batched_chunk_cost_step")

    def __init__(self, jax, driver_module):
        self.jax = jax
        self.recorded = []
        for name in self._FACTORIES:
            setattr(driver_module, name,
                    self._wrap(getattr(driver_module, name)))

    def _wrap(self, factory):
        def build(*args, **kwargs):
            entry = {"fn": factory(*args, **kwargs), "args": None}
            self.recorded.append(entry)

            def step(*call_args):
                if entry["args"] is None:
                    entry["args"] = self.jax.tree.map(self._spec, call_args)
                return entry["fn"](*call_args)
            return step
        return build

    def _spec(self, x):
        if isinstance(x, self.jax.Array):
            return self.jax.ShapeDtypeStruct(x.shape, x.dtype,
                                             sharding=x.sharding)
        return x

    def take_hlo(self):
        """Compiled HLO of the programs recorded since the last call (a
        persistent-cache hit: each was just compiled for its dispatch)."""
        texts = [e["fn"].lower(*e["args"]).compile().as_text()
                 for e in self.recorded if e["args"] is not None]
        self.recorded = []
        return texts

    def discard(self):
        self.recorded = []


def custom_calls(texts, families):
    """Whether each kernel family is a Mosaic custom call in any of the
    compiled programs (its pallas_call is named after the family; under
    vmap the instruction becomes "vmap_<name>")."""
    found = {}
    for fam in families:
        pat = re.compile(r"%[\w.\-]*" + fam + r"[\w.\-]* = [^\n]*"
                         r'custom_call_target="tpu_custom_call"')
        found[fam] = any(pat.search(t) for t in texts)
    return found


def rel_err(xs, refs) -> float:
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(r)))
                     / max(float(np.max(np.abs(np.asarray(r)))), 1e-30))
               for x, r in zip(xs, refs))


def leaf_devices(bundle) -> int:
    """Fewest devices any data leaf of the bundle is placed on."""
    import jax
    return min(len(leaf.sharding.device_set)
               for leaf in jax.tree.leaves(bundle.data))


# ---------------------------------------------------------------------
# Workloads
# ---------------------------------------------------------------------

class Smoke:
    def __init__(self, jax, seed: int):
        self.jax = jax
        self.seed = seed
        from repro.core import driver
        self.programs = ChunkPrograms(jax, driver)
        self.ok = True

    def _key(self, i: int):
        return self.jax.random.fold_in(self.jax.random.PRNGKey(self.seed),
                                       i)

    def reference(self, fn):
        """``fn`` with every kernel family on its jnp oracle and every
        matmul at full float32 precision."""
        from repro.kernels.common import reference_kernels
        with reference_kernels(), self.jax.default_matmul_precision(
                "highest"):
            return fn()

    def report(self, name, *, xs, err, tol, why, extra, hlo):
        from repro.kernels.common import kernel_fallbacks
        checks_ok = extra.pop("ok", True)
        fams = custom_calls(hlo, FAMILIES_OF[name])
        fallbacks = list(kernel_fallbacks())
        finite = all(np.isfinite(np.asarray(x)).all() for x in xs)
        ok = bool(checks_ok and finite and err <= tol and not fallbacks
                  and all(fams.values()))
        line = {"phase": name, "ok": ok, **extra,
                "max_rel_err": err, "tol": tol, "tol_reason": why,
                "finite": finite, "kernel_fallbacks": fallbacks,
                "tpu_custom_call": fams}
        print(json.dumps(line), flush=True)
        self.ok &= ok

    def solve(self, *args, chunk=None, **kwargs):
        from repro.core.problem import solve
        sol = solve(*args, chunk=chunk or CHUNK, **kwargs)
        xs = [np.asarray(x) for x in self.jax.tree.leaves(sol.x)]
        return sol, xs, {"iters": len(sol.log.costs)}

    # ------------------------------------------------------- phases
    def population(self, n):
        from repro.imaging import psf as psf_op
        return psf_op.simulate(n, self._key(0))

    def deconvolution(self, data, mode, mesh=None, hlo=True):
        """Returns (result, extra, compiled chunk HLO if ``hlo``)."""
        from repro.imaging.condat import SolverConfig
        cfg = SolverConfig(mode=mode, n_scales=4, max_iter=ITERS, tol=0.0)
        sol, xs, ran = self.solve(
            "deconvolve", data.Y, data.psfs, cfg=cfg, mesh=mesh)
        extra = dict(stamps=int(data.Y.shape[0]),
                     stamp=int(data.Y.shape[-1]), n_scales=4, **ran,
                     data_leaf_devices=leaf_devices(sol.bundle))
        check_operator(extra, xs[0], sol.bundle.data["HX"], data.psfs)
        del sol
        return xs, extra, self.chunk_hlo(hlo)

    def deconvolution_ref(self, data, mode):
        xs, _, _ = self.reference(
            lambda: self.deconvolution(data, mode, hlo=False))
        return xs

    def chunk_hlo(self, wanted: bool):
        if wanted:
            return self.programs.take_hlo()
        self.programs.discard()
        return []

    def scdl_data(self):
        from repro.data.synthetic import coupled_patches
        return coupled_patches(SCDL_K, 289, 81, 512, seed=self.seed)

    def scdl(self, S_h, S_l, mesh=None, hlo=True):
        """Returns ([NRMSE trajectory], dictionaries, extra, hlo)."""
        from repro.imaging.scdl import SCDLConfig
        cfg = SCDLConfig(n_atoms=512, max_iter=ITERS)
        sol, xs, ran = self.solve("scdl", S_h, S_l, cfg=cfg,
                                           mesh=mesh)
        extra = dict(P=S_h.shape[0], M=S_l.shape[0], A=512,
                     K=S_h.shape[1], **ran,
                     nrmse_first_last=[sol.log.costs[0], sol.log.costs[-1]],
                     data_leaf_devices=leaf_devices(sol.bundle))
        costs = [np.asarray(sol.log.costs)]
        del sol
        return costs, xs, extra, self.chunk_hlo(hlo)

    def scdl_ref(self, S_h, S_l):
        costs, dicts, _, _ = self.reference(
            lambda: self.scdl(S_h, S_l, hlo=False))
        return costs, dicts

    def serve(self):
        from repro.imaging import psf as psf_op
        from repro.imaging.condat import SolverConfig
        from repro.serve import ServeConfig
        from repro.serve.service import AsyncSolveService, SolveRequest
        cfg = SolverConfig(mode="sparse", n_scales=4, max_iter=2 * CHUNK,
                           tol=0.0)
        options = dict(chunk=CHUNK)
        pops = [psf_op.simulate(n, self._key(10 + i))
                for i, n in enumerate(SERVE_STAMPS)]

        async def drive():
            svc = AsyncSolveService(ServeConfig(batch_window_s=0.5,
                                                max_batch=8))
            async with svc:
                recs = [await svc.submit(SolveRequest(
                    "deconvolve", (d.Y, d.psfs), cfg=cfg,
                    options=options)) for d in pops]
                return [await svc.result(r.id, timeout=900) for r in recs]

        recs = asyncio.run(drive())
        hlo = self.chunk_hlo(True)
        done = all(r.status == "done" for r in recs)
        xs = ([np.asarray(r.solution.x) for r in recs] if done else [])
        refs = [self.reference(lambda d=d: np.asarray(
            self.solve("deconvolve", d.Y, d.psfs, cfg=cfg,
                             **options)[0].x)) for d in pops]
        self.chunk_hlo(False)
        extra = dict(
            ok=done and all(r.batch_size > 1 and not r.quarantined
                            for r in recs),
            requests=len(recs), status=[r.status for r in recs],
            errors=[r.error for r in recs if r.error],
            batch_sizes=[r.batch_size for r in recs])
        return xs or [np.full(1, np.nan)], refs[:len(xs) or 1], extra, hlo

    def completion(self):
        """A rank-8 matrix the size of the stamp population's pixel
        matrix (one 41x41 stamp per row), 60% of entries observed.
        Returns the recovered matrix, the relative Frobenius error of
        its unobserved entries against the known matrix, extra, hlo."""
        from repro.imaging.lowrank import CompletionConfig
        k1, k2, k3 = self.jax.random.split(self._key(20), 3)
        n, p, r = STAMPS, 41 * 41, 8
        jnp = self.jax.numpy
        Y = (self.jax.random.normal(k1, (n, r))
             @ self.jax.random.normal(k2, (r, p))) / np.sqrt(r)
        M = (self.jax.random.uniform(k3, (n, p)) < 0.6).astype(jnp.float32)
        # the range finder must overshoot the rank: between SVTs the
        # masked residual raises the iterate's rank (a 32-column finder
        # stays at 0.4 recovery error after 24 steps)
        cfg = CompletionConfig(rank=COMPLETION_RANK, oversample=
                               COMPLETION_RANK, max_iter=ITERS, tol=0.0)
        sol, xs, ran = self.solve("lowrank", Y, M, cfg=cfg)
        del sol
        hidden = np.asarray(M) == 0
        Y = np.asarray(Y)
        err = float(np.linalg.norm((xs[0] - Y)[hidden])
                    / np.linalg.norm(Y[hidden]))
        extra = dict(n=n, p=p, true_rank=r, observed=0.6, **ran)
        self.svt_check(extra, n, p, COMPLETION_RANK, 2 * COMPLETION_RANK)
        return xs, err, extra, self.chunk_hlo(True)

    def svt_check(self, extra, n, p, rank, columns):
        """The randomized SVT on the chip against the exact SVT in
        float64 on the host, on an (n, p) matrix of the range finder's
        rank with known singular vectors, thresholded at its median
        singular value; recorded in ``extra``."""
        from repro.imaging import lowrank as lr
        rng = np.random.default_rng(self.seed)
        U, _ = np.linalg.qr(rng.standard_normal((n, rank)))
        V, _ = np.linalg.qr(rng.standard_normal((p, rank)))
        s = np.geomspace(1.0, 0.05, rank) * np.sqrt(n * p)
        t = float(np.median(s))
        A = (U * s) @ V.T
        exact = (U * np.maximum(s - t, 0.0)) @ V.T
        omega = lr.make_test_matrix(p, rank, columns - rank)
        got = self.jax.jit(lr.randomized_svt_local)(
            self.jax.numpy.asarray(A, np.float32), omega, t)
        err = rel_err([got], [exact])
        extra.update(svt_f64_err=err, svt_tol=SVT_TOL[0],
                     svt_tol_reason=SVT_TOL[1])
        extra["ok"] = extra.get("ok", True) and err <= SVT_TOL[0]


# ---------------------------------------------------------------------
# Runs
# ---------------------------------------------------------------------

DECONV_TOL = (1e-4, "both paths run the same fp32 elementwise math and "
              "DFT products, only grouped differently; on CPU a 1-ulp "
              "change of the input moves the result by 3e-7")
LOWRANK_TOL = (1e-3, "the randomized SVT's Gram squares the conditioning "
               "of its products; at full fp32 precision a 1-ulp change of "
               "the input moves the iterate by 2e-6 to 4e-6 on CPU, while "
               "one bf16 pass per dot moves it by 1e-2")
OPERATOR_TOL = (1e-5, "fp32 DFT products at full precision over 81-point "
                "grids: 1.5e-7 from float64 on CPU; the chip's FFT "
                "was 0.25 off at 5000 stamps and more")
SVT_TOL = (1e-4, "fp32 range finder on a matrix of its own rank with "
           "singular values spanning 20x: 1.6e-6 to 1.7e-6 from the float64 "
           "SVT on CPU at these shapes; one bf16 pass per product would "
           "round at about 4e-3")
COMPLETION_TOL = (5e-2, "recovery of the unobserved 40% after 24 "
                  "proximal-gradient steps, set by convergence, not "
                  "rounding: 6.8e-3 on CPU; zero fill is 1.0, and a "
                  "32-column range finder stays at 0.4")
COMPLETION_RANK = 24
SCDL_TOL = (1e-3, "NRMSE trajectory: on CPU a 1-ulp change of the input "
            "moves it by 3e-5; 1e-3 leaves room for the chip's other fp32 "
            "grouping of the same sums")
SCDL_DICT_TOL = (5e-2, "the dictionaries are not unique along directions "
                 "the objective does not see (on CPU a 1-ulp change of the "
                 "input moves them by 7e-3 of their largest entry), and "
                 "dict_outer's in-kernel f32 dot takes one bf16 pass")


def check_operator(extra, X, HX, psfs) -> None:
    """The PSF operator as the chunk program computed it — the carried
    ``HX`` is H applied to the final iterate — against the same
    convolution in float64 numpy on the host."""
    from repro.imaging.psf import pad_for
    X, psfs = np.asarray(X, np.float64), np.asarray(psfs, np.float64)
    s, h = X.shape[-1], psfs.shape[-1]
    pad = pad_for(s, h)
    grid = np.zeros(psfs.shape[:-2] + (pad, pad))
    grid[..., :h, :h] = psfs
    kf = np.fft.rfft2(np.roll(grid, (-(h // 2), -(h // 2)), axis=(-2, -1)))
    ref = np.fft.irfft2(np.fft.rfft2(X, s=(pad, pad)) * kf,
                        s=(pad, pad))[..., :s, :s]
    err = rel_err([np.asarray(HX)], [ref])
    extra.update(operator_f64_err=err, operator_tol=OPERATOR_TOL[0],
                 operator_tol_reason=OPERATOR_TOL[1])
    extra["ok"] = extra.get("ok", True) and err <= OPERATOR_TOL[0]


def check_dictionaries(extra, dicts, refs) -> None:
    """The dictionaries ride along the NRMSE trajectory that the phase
    compares, under their own, wider tolerance."""
    err = rel_err(dicts, refs)
    extra.update(dict_max_rel_err=err, dict_tol=SCDL_DICT_TOL[0],
                 dict_tol_reason=SCDL_DICT_TOL[1])
    extra["ok"] = extra.get("ok", True) and err <= SCDL_DICT_TOL[0]


def run_one_chip(sm: Smoke) -> None:
    from repro.imaging.condat import SolverConfig
    data = sm.population(STAMPS)
    for name, mode, (tol, why) in (
            ("sparse_deconvolution", "sparse", DECONV_TOL),
            ("lowrank_deconvolution", "lowrank", LOWRANK_TOL)):
        xs, extra, hlo = sm.deconvolution(data, mode)
        refs = sm.deconvolution_ref(data, mode)
        if mode == "lowrank":
            rank = SolverConfig().rank
            sm.svt_check(extra, STAMPS, 41 * 41, rank, rank + 8)
        sm.report(name, xs=xs, err=rel_err(xs, refs), tol=tol, why=why,
                  extra=extra, hlo=hlo)
    del data

    S_h, S_l = sm.scdl_data()
    xs, dicts, extra, hlo = sm.scdl(S_h, S_l)
    refs, ref_dicts = sm.scdl_ref(S_h, S_l)
    check_dictionaries(extra, dicts, ref_dicts)
    sm.report("scdl", xs=xs, err=rel_err(xs, refs), tol=SCDL_TOL[0],
              why=SCDL_TOL[1], extra=extra, hlo=hlo)
    del S_h, S_l

    xs, refs, extra, hlo = sm.serve()
    sm.report("serve", xs=xs, err=rel_err(xs, refs), tol=DECONV_TOL[0],
              why=DECONV_TOL[1], extra=extra, hlo=hlo)

    xs, err, extra, hlo = sm.completion()
    sm.report("lowrank_completion", xs=xs, err=err,
              tol=COMPLETION_TOL[0], why=COMPLETION_TOL[1], extra=extra,
              hlo=hlo)


def run_four_chips(sm: Smoke) -> None:
    """Sparse deconvolution and SCDL over a (4, 1) data mesh, each
    compared with the same inputs solved on one device."""
    from repro.launch.mesh import smallest_mesh
    mesh = smallest_mesh()
    n_dev = len(sm.jax.devices())
    where = {"devices": n_dev, "mesh": dict(mesh.shape)}

    data = sm.population(STAMPS)
    xs, extra, hlo = sm.deconvolution(data, "sparse", mesh=mesh)
    extra.update(where, ok=extra["data_leaf_devices"] == n_dev)
    refs, _, _ = sm.deconvolution(data, "sparse", hlo=False)
    sm.report("sparse_deconvolution", xs=xs, err=rel_err(xs, refs),
              tol=DECONV_TOL[0],
              why="mesh vs one device: " + DECONV_TOL[1], extra=extra,
              hlo=hlo)
    del data

    S_h, S_l = sm.scdl_data()
    xs, dicts, extra, hlo = sm.scdl(S_h, S_l, mesh=mesh)
    # the Gram/outer-product psums must cross chips
    extra.update(where, all_reduces=sum(t.count(" all-reduce(")
                                        for t in hlo))
    extra["ok"] = (extra["data_leaf_devices"] == n_dev
                   and extra["all_reduces"] > 0)
    refs, ref_dicts, _, _ = sm.scdl(S_h, S_l, hlo=False)
    check_dictionaries(extra, dicts, ref_dicts)
    sm.report("scdl", xs=xs, err=rel_err(xs, refs), tol=SCDL_TOL[0],
              why="mesh vs one device (psum order differs): "
                  + SCDL_TOL[1], extra=extra, hlo=hlo)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-mesh path and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data")
    args = ap.parse_args()

    forced = os.environ.get("REPRO_FORCE_INTERPRET", "")
    if forced.strip().lower() not in ("", "0", "false", "no"):
        _fail("REPRO_FORCE_INTERPRET is set; the smoke test checks the "
              "compiled kernels and refuses to run them interpreted")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX found {devices[0].platform} devices only")
    if len(devices) != args.chips:
        _fail(f"--chips {args.chips} but JAX found {len(devices)} TPU "
              f"devices; expose exactly that many")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(json.dumps({"compile_cache": cache_dir}), flush=True)

    sm = Smoke(jax, args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(sm)
    else:
        run_one_chip(sm)
    print(json.dumps({"total_s": time.perf_counter() - t0}), flush=True)
    if not sm.ok:
        _fail("a phase failed (see its line above)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
